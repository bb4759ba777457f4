"""The grouped-head paged-decode kernel (`ops.grouped_decode`, interpreted
on the CPU) against `diff_attention.dense` over the rows gathered through
the page table; where `P`, the pages a grid step takes, comes from;
`diff_attention.paged_reader`'s gate; and that no stack without
grouped-head K/V pages imports the kernel's module or compiles another
program for it."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention as A
from paddle_tpu.ops import diff_attention as DA
from paddle_tpu.ops import grouped_decode as GD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: key-value heads (two wide heads of 128 lanes), query heads (four a
#: wide head), head size, page rows, pages a slot: two pages a grid step,
#: so four blocks a slot
HKV, HQ, D, PSZ, MP = 4, 8, 64, 8, 8
W = HKV * D
P = 2


def _gathered(q, k_pages, v_pages, table, n_keys):
    """`dense` over the rows gathered through the table."""
    S = table.shape[0]
    return DA.dense(q, k_pages[table].reshape(S, -1, W),
                    v_pages[table].reshape(S, -1, W), HKV, n_keys)


def _pool(seed, lengths, dtype, permute=True):
    """Slots of `lengths` written rows on pages scattered over the pool
    (in table order where not `permute`), the table's unwritten entries
    on the trash row (which holds numbers: inactive slots write there),
    and one inactive slot last: its whole table on the trash row, one row
    read (index 0 + 1)."""
    rs = np.random.RandomState(seed)
    S = len(lengths) + 1
    N = S * MP
    order = rs.permutation(N) if permute else np.arange(N)
    table = order.reshape(S, MP).astype(np.int32)
    lengths = list(lengths) + [1]
    for s, n in enumerate(lengths):
        table[s, -(-n // PSZ):] = N
    table[-1] = N
    k_pages = jnp.asarray(rs.randn(N + 1, PSZ, W), dtype)
    v_pages = jnp.asarray(rs.randn(N + 1, PSZ, W), dtype)
    q = jnp.asarray(rs.randn(S, HQ, D), dtype)
    return (q, k_pages, v_pages, jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


def _kernel(q, k_pages, v_pages, table, n_keys):
    """The kernel under the TPU interpreter, waited for: nothing else is
    dispatched while its callbacks run (an eager op dispatched beside
    them can deadlock the CPU client)."""
    return jax.block_until_ready(GD.paged_grouped_decode(
        DA.widen_queries(q, HKV), k_pages, v_pages, table, n_keys,
        1.0 / np.sqrt(D), interpret=True))


@pytest.fixture
def two_pages_a_block(monkeypatch):
    """The buffer budget that gives `P` pages a grid step at these
    shapes (the kernel's grid then has blocks past a short slot's pages,
    and a last block that is partly written)."""
    def set_for(dtype):
        page = PSZ * W * np.dtype(dtype).itemsize
        monkeypatch.setattr(GD, "_GROUPED_BUFFER_BYTES", 4 * P * page)
        assert GD.block_pages(PSZ, W, MP, dtype) == P

    return set_for


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    ("one row", 1, True), ("a page", PSZ, True),
    ("a page and one", PSZ + 1, True), ("a block", P * PSZ, True),
    ("a block and one", P * PSZ + 1, True), ("max_len", MP * PSZ, True),
    ("a block and one, table in order", P * PSZ + 1, False)],
    ids=lambda c: c[0].replace(" ", "_").replace(",", ""))
def test_kernel_equals_dense_over_gathered_rows(two_pages_a_block, case,
                                                dtype):
    """A slot of `n_keys` written rows (one row; a page; a page and one;
    a block of P pages; a block and one; the whole table at max_len)
    beside a slot at max_len and an inactive slot on the trash row, the
    pages scattered over the pool by a permuted table (and once in table
    order). float32 pages at 1e-5. With bfloat16 pages both outputs are
    rounded to 8 bits (and the kernel's weights against a running
    maximum where `dense`'s are against the slot's), so each is held to
    the float32 composition over the same values: the kernel lies as
    close to it as the bfloat16 composition does."""
    _, n_keys, permute = case
    two_pages_a_block(dtype)
    args = _pool(n_keys, [n_keys, MP * PSZ], dtype, permute)
    got = _kernel(*args)
    want = _gathered(*args)
    assert got.shape == (3, HKV // 2, 2 * HQ // HKV, 2 * D)
    assert got.dtype == args[0].dtype
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    assert np.isfinite(got).all()
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    else:
        exact = np.asarray(_gathered(*(
            x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
            for x in args)))
        assert np.abs(got - exact).max() <= \
            1.25 * np.abs(want - exact).max()


def test_an_empty_slot_reads_zeros_and_leaves_the_others(
        two_pages_a_block):
    """A slot of no written row (its table on the trash row, length 0)
    computes nothing and reads zeros, and the slots beside it read what
    they read without it."""
    two_pages_a_block("float32")
    q, kp, vp, table, n = _pool(3, [PSZ + 1, 0], "float32")
    got = np.asarray(_kernel(q, kp, vp, table, n))
    assert (got[1] == 0).all()
    want = np.asarray(_gathered(q, kp, vp, table, n))
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=1e-5, rtol=0)


def test_block_of_pages_from_the_shapes():
    """The Phi-4-mini-flash pool (16-row pages of 1,280 bfloat16 values:
    ten wide heads of 128 lanes; 256 pages a slot) takes the kernel and
    32 pages a grid step, the four buffers 5 MiB; the rehearsal's pool
    (4-row pages of 16 float32 lanes) and rows whose wide heads are not
    128 lanes take the gather, which the engine counts as a page a
    block."""
    assert GD.kernel_fits(16, 1280, 128, "bfloat16")
    assert GD.block_pages(16, 1280, 256, "bfloat16") == 32
    assert GD.decode_block_pages(16, 1280, 128, 256, "bfloat16") == 32
    assert GD.decode_block_pages(16, 1280, 128, 8, "bfloat16") == 8
    assert GD.decode_block_pages(16, 1280, 128, 256, "float32") == 16
    assert not GD.kernel_fits(4, 16, 16, "float32")
    assert GD.decode_block_pages(4, 16, 16, 16, "float32") == 1
    assert not GD.kernel_fits(16, 1280, 256, "bfloat16")
    assert not GD.kernel_fits(16, 1200, 128, "bfloat16")


def _interpreted(monkeypatch):
    """The kernel's entry point under the TPU interpreter; returns the
    calls it got."""
    calls = []
    kernel = GD.paged_grouped_decode

    def counted(*a, **k):
        calls.append(1)
        return kernel(*a, **k, interpret=True)

    monkeypatch.setattr(GD, "paged_grouped_decode", counted)
    return calls


def _on_chip(monkeypatch):
    """`paged_reader` as the chip traces it: the backend asked for is a
    TPU's and the kernel runs under the TPU interpreter. Returns the
    calls the kernel got."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    return _interpreted(monkeypatch)


def test_the_reader_takes_the_kernel_where_it_opens(monkeypatch,
                                                    two_pages_a_block):
    """On the chip, over a pool the kernel fits, every layer's read is a
    kernel call and equals `dense` over the gathered rows; a table rolled
    by one slot (the cell's planted fault, which wraps `paged_reader`)
    reaches the kernel and changes what the slots read. Under
    `PT_FLASH_ATTENTION=0` and over rows of narrower wide heads the
    reader gathers."""
    two_pages_a_block("float32")
    calls = _on_chip(monkeypatch)
    q, kp, vp, table, n = _pool(5, [PSZ + 1, MP * PSZ], "float32")
    read = DA.paged_reader(kp, vp, table, HKV)
    got = np.asarray(read(q, n))
    assert len(calls) == 1
    np.testing.assert_allclose(
        got, np.asarray(_gathered(q, kp, vp, table, n)), atol=1e-5, rtol=0)
    rolled = np.asarray(DA.paged_reader(kp, vp, jnp.roll(table, 1, 0),
                                        HKV)(q, n))
    assert len(calls) == 2
    assert np.abs(rolled - got).max() > 0.1

    monkeypatch.setenv("PT_FLASH_ATTENTION", "0")
    DA.paged_reader(kp, vp, table, HKV)(q, n)
    monkeypatch.delenv("PT_FLASH_ATTENTION")
    narrow = _pool(5, [PSZ + 1], "float32")
    DA.paged_reader(narrow[1], narrow[2], narrow[3], 2 * HKV)(
        narrow[0].reshape(2, 2 * HQ, D // 2), narrow[4])
    assert len(calls) == 2


def _serve_phi(monkeypatch, through_kernel):
    """Three requests served by a Phi-4-flash-shaped model whose K/V rows
    are two wide heads of 128 lanes (4 key-value heads of 64, 8 query
    heads), on 8-row pages, two pages a grid step: the tokens and every
    logits row the served programs computed, and the engine. Where
    `through_kernel`, `paged_reader` is traced as on the chip (the
    backend a TPU's while the reader is built, the kernel interpreted).
    Iterations run one after another (`run_iteration`), each reading its
    step's tokens before the next is dispatched, for `_kernel`'s
    reason."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import Request, Scheduler, ServingEngine
    from paddle_tpu.text import models as M

    page = 8 * 256 * 4
    monkeypatch.setattr(GD, "_GROUPED_BUFFER_BYTES", 4 * 2 * page)
    if through_kernel:
        calls = _interpreted(monkeypatch)
        reader = DA.paged_reader

        def on_chip(*a):
            with monkeypatch.context() as m:
                m.setattr(A, "_on_tpu", lambda: True)
                return reader(*a)

        monkeypatch.setattr(DA, "paged_reader", on_chip)
    paddle.seed(5)
    model = M.Phi4FlashForCausalLM(M.Phi4FlashConfig.tiny(
        hidden_size=512, num_attention_heads=8, num_key_value_heads=4))
    model.eval()
    seen = []
    plain = model._logits

    def tapped(y):
        lg = plain(y)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), lg)
        return lg

    model._logits = tapped
    eng = ServingEngine(model, paged=True, num_slots=2, max_len=64,
                        page_size=8)
    sch = Scheduler(max_queue=4)
    rs = np.random.RandomState(2)
    reqs = [Request(rs.randint(0, 96, (p,)).astype(np.int32), None,
                    max_new_tokens=n, eos_id=None)
            for p, n in ((5, 14), (17, 9), (9, 20))]
    for r in reqs:
        sch.submit(r)
    for _ in range(200):
        if sch.depth() == 0 and eng.idle():
            break
        eng.run_iteration(sch)
    assert all(r.state == "DONE" for r in reqs)
    model._logits = plain
    assert not through_kernel or calls
    return [list(r.tokens) for r in reqs], seen, eng


def test_served_steps_through_the_kernel_give_the_gathers_logits(
        monkeypatch):
    """The kernel inside the served decode step, reading the pages that
    `write_token` wrote through the engine's table (slots reused, lengths
    past one block of two pages), gives the tokens and the logits of the
    same model read through the gather; the engine reports the kernel's
    two pages a block."""
    want_toks, want_rows, eng = _serve_phi(monkeypatch, False)
    assert eng._pages_per_block == 2
    with monkeypatch.context() as m:
        got_toks, got_rows, _ = _serve_phi(m, True)
    assert got_toks == want_toks
    assert len(got_rows) == len(want_rows)
    for got, want in zip(got_rows, want_rows):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


#: what a process builds and serves in the footprint test: a stack of the
#: `bart_large_dec` cell's kind (decoder layers with cross attention over
#: a memory, K/V pages), then a DeepSeek-V3-shaped causal LM (latent
#: pages), then a `phi4_mini_flash`-shaped one; after each, whether the
#: grouped kernel's module is loaded and which programs the engine traced
_FOOTPRINT = textwrap.dedent("""
    import json, sys
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.serving import Request, Scheduler, ServingEngine
    from paddle_tpu.text import models as M

    MOD = "paddle_tpu.ops.grouped_decode"
    out = {"import": MOD in sys.modules}

    def serve(eng, memory):
        sch = Scheduler(max_queue=4)
        rs = np.random.RandomState(0)
        for p in (3, 6):
            sch.submit(Request(rs.randint(2, 17, (p,)).astype(np.int32),
                               memory, max_new_tokens=4, eos_id=None))
        eng.serve_until_idle(sch)
        return sorted(str(k) for k in eng.trace_counts)

    np.random.seed(7)
    dec = nn.TransformerDecoder(
        nn.TransformerDecoderLayer(128, 2, 64, dropout=0.0), 2)
    dec.eval()
    eng = ServingEngine(dec, nn.Embedding(17, 128), nn.Linear(128, 17),
                        num_slots=2, max_len=32, paged=True, page_size=8)
    out["bart_large_dec"] = serve(
        eng, np.random.RandomState(1).randn(4, 128).astype("f4"))
    out["after_bart_large_dec"] = MOD in sys.modules
    for name, cls, cfg in (
            ("deepseek_v3", M.DeepseekV3ForCausalLM, M.DeepseekV3Config),
            ("phi4_mini_flash", M.Phi4FlashForCausalLM, M.Phi4FlashConfig)):
        paddle.seed(3)
        model = cls(cfg.tiny())
        model.eval()
        eng = ServingEngine(model, paged=True, num_slots=2, max_len=32,
                            page_size=4)
        out[name] = serve(eng, None)
        out["after_" + name] = MOD in sys.modules
        out["pages_per_block_" + name] = eng._pages_per_block
    print(json.dumps(out))
""")


def test_no_other_stack_loads_the_grouped_kernel():
    """`import paddle_tpu`, a paged engine over a decoder stack with K/V
    pages and one over a DeepSeek-V3-shaped causal LM, each serving two
    requests in a fresh process, leave `ops.grouped_decode` unloaded and
    trace the programs they traced before the kernel existed; the
    Phi-4-flash-shaped stack loads it (the check sees a load where there
    is one: its engine asks the module for `pages_per_block`, 1 at the
    tiny preset's rows, which the kernel does not take)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [ROOT] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    env.pop("XLA_FLAGS", None)
    run = subprocess.run([sys.executable, "-c", _FOOTPRINT], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    assert out["import"] is False
    assert out["after_bart_large_dec"] is False
    assert out["after_deepseek_v3"] is False
    assert out["after_phi4_mini_flash"] is True
    assert out["pages_per_block_phi4_mini_flash"] == 1
    assert out["bart_large_dec"] == [
        "('cow',)", "('pjoin', 4)", "('pjoin', 8)",
        "('pstep', 2, 32, 4, 128, 'float32', 8, 8, 'None')"]
    assert out["phi4_mini_flash"] == out["deepseek_v3"] == [
        "('pjoin', 4)", "('pjoin', 8)",
        "('pstep', 2, 32, 0, 0, 'float32', 4, 16, 'None')"]


def test_the_kernel_reads_a_slot_under_its_own_table(two_pages_a_block):
    """A slot's output depends on its own query, table row and length
    alone: swapping two slots' swaps their outputs."""
    two_pages_a_block("float32")
    q, kp, vp, table, n = _pool(9, [PSZ + 3, 2 * P * PSZ], "float32")
    got = np.asarray(_kernel(q, kp, vp, table, n))
    swap = jnp.array([1, 0, 2])
    swapped = np.asarray(_kernel(q[swap], kp, vp, table[swap], n[swap]))
    np.testing.assert_allclose(swapped[[1, 0, 2]], got, atol=1e-6, rtol=0)
