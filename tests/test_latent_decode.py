"""The latent paged-decode kernel (`ops.latent_decode`, interpreted on the
CPU) against the absorbed attention's composition over rows gathered
through the page table; where `P`, the pages a grid step takes, comes
from; and that no stack without latent pages imports the kernel's
module or compiles another program for it."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.layer.mla import _exp_and_sum
from paddle_tpu.ops import latent_decode as LD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: heads, latent rank, rotary width, row width (closed with zeros), page
#: rows, pages a slot: two pages a grid step, so four blocks a slot
H, RANK, ROT, W, PSZ, MP = 8, 64, 32, 128, 16, 8
P = 2
SCALE = 0.3


def _composed(qa, pages, table, n_keys):
    """`LatentAttention.absorbed`'s products over the gathered rows."""
    f32 = jnp.float32
    rows = pages[table].reshape(table.shape[0], -1, pages.shape[-1])
    sc = jnp.einsum("shc,slc->shl", qa, rows[..., :RANK + ROT],
                    preferred_element_type=f32) * f32(SCALE)
    live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None] < n_keys[:, None]
    e, total = _exp_and_sum(jnp.where(live[:, None], sc, f32(-1e30)))
    o = jnp.einsum("shl,slc->shc", e.astype(rows.dtype), rows[..., :RANK],
                   preferred_element_type=f32)
    return (o / total).astype(rows.dtype)


def _pool(seed, lengths, dtype):
    """Slots of `lengths` written rows on pages scattered over the pool,
    the table's unwritten entries on the trash row (which holds numbers:
    inactive slots write there), and one inactive slot last: its whole
    table on the trash row, one row read (index 0 + 1)."""
    rs = np.random.RandomState(seed)
    S = len(lengths) + 1
    N = S * MP
    table = rs.permutation(N).reshape(S, MP).astype(np.int32)
    lengths = list(lengths) + [1]
    for s, n in enumerate(lengths):
        table[s, -(-n // PSZ):] = N
    table[-1] = N
    pages = jnp.asarray(rs.randn(N + 1, PSZ, W), dtype)
    pages = pages.at[:, :, RANK + ROT:].set(0)       # the row's closing zeros
    qa = jnp.asarray(rs.randn(S, H, RANK + ROT), dtype)
    return qa, pages, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_keys", [1, 15, 16, 17, P * PSZ, P * PSZ + 1,
                                    MP * PSZ])
def test_kernel_equals_the_composition_over_gathered_rows(monkeypatch,
                                                          n_keys, dtype):
    """A slot of `n_keys` written rows (one row; a page less one, a page,
    a page and one; a block of P pages, a block and one; the whole
    table) beside a slot of the whole table and an inactive slot. float32
    pages at `test_absorbed_attention_equals_unabsorbed`'s 1e-5. With
    bfloat16 pages both outputs are rounded to 8 bits (and the kernel's
    weights against a running maximum where the composition's are
    against the slot's), so each is held to the float32 composition over
    the same values: the kernel lies as close to it as the bfloat16
    composition does."""
    page = PSZ * W * np.dtype(dtype).itemsize
    monkeypatch.setattr(LD, "_LATENT_BUFFER_BYTES", 2 * P * page)
    assert LD.block_pages(PSZ, W, MP, dtype) == P
    qa, pages, table, n = _pool(n_keys, [n_keys, MP * PSZ], dtype)
    got = LD.paged_latent_decode(qa, pages, table, n, RANK, SCALE,
                                 interpret=True)
    want = _composed(qa, pages, table, n)
    assert got.shape == (3, H, RANK) and got.dtype == pages.dtype
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    assert np.isfinite(got).all()
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    else:
        exact = np.asarray(_composed(qa.astype(jnp.float32),
                                     pages.astype(jnp.float32), table, n))
        assert np.abs(got - exact).max() <= \
            1.25 * np.abs(want - exact).max()


def test_block_of_pages_from_the_shapes():
    """The DeepSeek-V3 pool (16-row pages of 640 bfloat16 values, 512
    latent, 128 heads, 384 pages a slot) takes 32 pages a grid step, the
    two buffers 1.25 MiB; a pool whose rows do not tile takes the gather,
    which the engine counts as a page a block."""
    assert LD.kernel_fits(16, 640, 512, 128, "bfloat16")
    assert LD.decode_block_pages(16, 640, 512, 128, 384, "bfloat16") == 32
    assert LD.decode_block_pages(16, 640, 512, 128, 8, "bfloat16") == 8
    assert LD.decode_block_pages(16, 640, 512, 128, 384, "float32") == 16
    assert LD.decode_block_pages(4, 32, 16, 4, 8, "float32") == 1
    assert not LD.kernel_fits(16, 576, 512, 128, "bfloat16")


#: what a process builds and serves in the footprint test: a stack of the
#: `bart_large_dec` cell's kind (decoder layers with cross attention over
#: a memory, K/V pages), then a `phi4_mini_flash`-shaped causal LM, then
#: a DeepSeek-V3 one; after each, whether the latent kernel's module is
#: loaded and which programs the engine traced
_FOOTPRINT = textwrap.dedent("""
    import json, sys
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.serving import Request, Scheduler, ServingEngine
    from paddle_tpu.text import models as M

    MOD = "paddle_tpu.ops.latent_decode"
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
            ("phi4_mini_flash", M.Phi4FlashForCausalLM, M.Phi4FlashConfig),
            ("deepseek_v3", M.DeepseekV3ForCausalLM, M.DeepseekV3Config)):
        paddle.seed(3)
        model = cls(cfg.tiny())
        model.eval()
        eng = ServingEngine(model, paged=True, num_slots=2, max_len=32,
                            page_size=4)
        out[name] = serve(eng, None)
        out["after_" + name] = MOD in sys.modules
    print(json.dumps(out))
""")


def test_no_other_stack_loads_the_latent_kernel():
    """`import paddle_tpu`, a paged engine over a decoder stack with K/V
    pages and one over a Phi-4-flash-shaped causal LM, each serving two
    requests in a fresh process, leave `ops.latent_decode` unloaded and
    trace the programs they traced before the kernel existed; the first
    latent stack loads it (the check sees a load where there is one)."""
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
    assert out["after_phi4_mini_flash"] is False
    assert out["after_deepseek_v3"] is True
    assert out["bart_large_dec"] == [
        "('cow',)", "('pjoin', 4)", "('pjoin', 8)",
        "('pstep', 2, 32, 4, 128, 'float32', 8, 8, 'None')"]
    assert out["phi4_mini_flash"] == out["deepseek_v3"] == [
        "('pjoin', 4)", "('pjoin', 8)",
        "('pstep', 2, 32, 0, 0, 'float32', 4, 16, 'None')"]
