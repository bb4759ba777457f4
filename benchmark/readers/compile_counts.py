"""Programs compiled (asked of the compiler and not read from the persistent
cache) from process start to window open: `CompileCounts`, fed by JAX's own
monitoring events."""


def read(facts):
    return facts["compile_counts_at_open"]["compiled"]
