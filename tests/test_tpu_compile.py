"""The Pallas kernels of the two main paths, compiled for a TPU v5e that
is described, not attached — at the shapes `chip_smoke.py` runs them at
(ERNIE-base attention b8 h12 s1024 d64; the Transformer-base paged pool
of 16 slots x 1024 positions, 8 heads of 64, 16-token pages).

Interpret mode cannot see what the chip's compiler refuses: a slice off
the tiling, too much fast memory, a literal Mosaic cannot legalize.
These compiles can, at no chip time, and they are what guards the
kernels now that no run-time probe falls back to the XLA reference.
Nothing runs, so results are the business of the interpret-mode parity
tests and of chip_smoke.py's kernel phase.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU's library, the suite runs under
several xdist workers, and each of them imports this file. The kernel
entry points are compiled directly — the dispatchers ask
`jax.default_backend()`, see the CPU and take the reference.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import attention as A
from paddle_tpu.ops import quant as Q

# the smoke's shapes
FLASH = dict(b=8, h=12, s=1024, d=64)
POOL = dict(S=16, h=8, L=1024, d=64, T=4, psz=16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Compile `fn` for the described chip from (shape, dtype) pairs;
    returns the compiled text, which must hold the Mosaic kernel."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
def test_flash_attention_fwd_bwd_compiles(one_chip, dtype, dropout):
    b, h, s, d = (FLASH[k] for k in "bhsd")
    qkv = ((b, h, s, d), jnp.dtype(dtype))

    def loss(q, k, v, bias, seed):
        out = A.flash_attention(q, k, v, bias, True, None,
                                dropout_p=dropout,
                                dropout_seed=seed if dropout else None)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2)), one_chip,
                        qkv, qkv, qkv, ((b, s), jnp.float32),
                        ((1,), jnp.int32))
    # fwd + dQ + dK/dV
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("has_bias", [False, True])
def test_flash_decode_and_verify_compile(one_chip, has_bias):
    S, h, L, d, T = (POOL[k] for k in ("S", "h", "L", "d", "T"))
    kv = ((S, h, L, d), jnp.float32)
    bias = ((S, L), jnp.float32) if has_bias else None
    _compile(A.flash_decode, one_chip, ((S, h, 1, d), jnp.float32), kv,
             kv, ((S,), jnp.int32), bias)
    _compile(A.flash_verify, one_chip, ((S, h, T, d), jnp.float32), kv,
             kv, ((S,), jnp.int32), bias)


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("T", [1, POOL["T"]])
def test_paged_flash_kernels_compile(one_chip, kv_dtype, T):
    S, h, L, d, psz = (POOL[k] for k in ("S", "h", "L", "d", "psz"))
    mp = L // psz
    pages = ((S * mp + 1, psz, h * d), jnp.dtype(kv_dtype))
    scale = (((S * mp + 1, 1, h), jnp.float32)
             if kv_dtype == "int8" else None)
    fn = A.paged_flash_decode if T == 1 else A.paged_flash_verify
    compiled = _compile(fn, one_chip, ((S, h, T, d), jnp.float32), pages,
                        pages, scale, scale, ((S, mp), jnp.int32),
                        ((S,), jnp.int32), ((S, L), jnp.float32))
    # the pool of the smoke must fit the chip with room for the model
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2**30
    # a grid step takes a block of P pages: (S, ceil(mp / P)) steps
    P = A._paged_block_pages(psz, h * d, T, mp, kv_dtype)
    assert P == 8 and _paged_grid(
        fn, ((S, h, T, d), jnp.float32), pages, pages, scale, scale,
        ((S, mp), jnp.int32), ((S,), jnp.int32),
        ((S, L), jnp.float32)) == (S, -(-mp // P))


# the benchmark's pool (`bart_large_dec`: 64 slots x 1024 positions,
# 16 heads of 64, 16-token float32 pages, a pad bias)
BENCH_POOL = dict(S=64, h=16, L=1024, d=64, psz=16)
# the most scratch a pool program may take at these shapes. With pages
# stored [pages + 1, heads, page_size, head_dim] the decode write + the
# kernel took 2,148,330,496 bytes: four lane-padded copies of a pool
BENCH_POOL_TEMP_LIMIT = 64 * 2**20


def _paged_grid(fn, *shapes):
    """The grid of the one pallas_call `fn` traces to at these (shape,
    dtype) pairs."""
    args = [None if s is None else jax.ShapeDtypeStruct(*s)
            for s in shapes]
    found = []

    def walk(jaxpr):
        for eq in jaxpr.eqns:
            if eq.primitive.name == "pallas_call":
                found.append(tuple(eq.params["grid_mapping"].grid))
            for sub in _sub_programs(eq):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(found) == 1, found
    return found[0]


def test_paged_flash_decode_compiles_at_benchmark_shapes(one_chip):
    S, h, L, d, psz = (BENCH_POOL[k] for k in ("S", "h", "L", "d", "psz"))
    mp = L // psz
    pages = ((S * mp + 1, psz, h * d), jnp.float32)
    shapes = (((S, h, 1, d), jnp.float32), pages, pages, None, None,
              ((S, mp), jnp.int32), ((S,), jnp.int32),
              ((S, L), jnp.float32))
    compiled = _compile(A.paged_flash_decode, one_chip, *shapes)
    # 512 grid steps a call, not one a (slot, page): a return to a
    # page a step fails here and not only in the benchmark
    P = A._paged_block_pages(psz, h * d, 1, mp, "float32")
    assert P == A._PAGED_BLOCK_PAGES == 8
    assert _paged_grid(A.paged_flash_decode, *shapes) == (S, mp // P) \
        == (64, 8)
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1
    # one output, merged over the pages in the kernel, in the pages'
    # row form [S, 1, h * d]: no `mp`-long partials
    result = calls[0].split("custom-call(")[0].split("=", 1)[1].strip()
    assert result.startswith(f"f32[{S},1,{h * d}]"), result
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        BENCH_POOL_TEMP_LIMIT


def _pool_copies(compiled, n_pages, psz, hd):
    """The `copy` operations of a compiled program whose result has the
    page pool's shape: what the compiler puts in when two users of the
    pool want it in two layouts."""
    pool = f"[{n_pages + 1},{psz},{hd}]"
    return [ln.strip() for ln in compiled.as_text().splitlines()
            if " copy(" in ln and pool in ln.split(" copy(")[0]]


@pytest.mark.parametrize("program", ["decode_step", "join_scatter"])
def test_pool_programs_copy_no_pool_at_benchmark_shapes(one_chip, program):
    """The mechanism of ROADMAP S1, held without a chip: with the pools
    donated, the decode step (the token write for K and V + the paged
    kernel) and the join scatter (a 512-token prompt's K and V into 32
    pages) update the page arrays in place: the compiled program holds
    no copy of a pool and next to no scratch."""
    from paddle_tpu.serving import paging as PG

    S, h, L, d, psz = (BENCH_POOL[k] for k in ("S", "h", "L", "d", "psz"))
    mp = L // psz
    n_pages = S * mp

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = sd((n_pages + 1, psz, h * d))

    def decode_step(kp, vp, q, ktok, vtok, tbl, idx, bias):
        kp, _ = PG.write_token(kp, None, tbl, idx, ktok)
        vp, _ = PG.write_token(vp, None, tbl, idx, vtok)
        return kp, vp, A.paged_flash_decode(q, kp, vp, None, None, tbl,
                                            idx + 1, bias)

    def join_scatter(kp, vp, ids, k, v):
        kp, _ = PG.write_prompt_pages(kp, None, ids, k, False)
        vp, _ = PG.write_prompt_pages(vp, None, ids, v, False)
        return kp, vp

    if program == "decode_step":
        args = (pages, pages, sd((S, h, 1, d)), sd((S, h, d)),
                sd((S, h, d)), sd((S, mp), jnp.int32), sd((S,), jnp.int32),
                sd((S, L)))
        fn = decode_step
    else:
        P = 512
        args = (pages, pages, sd((P // psz,), jnp.int32),
                sd((1, h, P, d)), sd((1, h, P, d)))
        fn = join_scatter
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    assert (program == "decode_step") == \
        ("tpu_custom_call" in compiled.as_text())
    assert not _pool_copies(compiled, n_pages, psz, h * d)
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        BENCH_POOL_TEMP_LIMIT


# the most scratch the whole decode step of the benchmark's pool may
# take (two layers of it; the scratch is a layer's, not the stack's).
# With pages stored heads-major `pstep` took 2.25 GB: three lane-padded
# copies of each pool a layer
BENCH_PSTEP_TEMP_LIMIT = 100 * 10**6
BENCH_PROGRAMS = ("pstep", "pjoin", "attach", "cow", "pattach")


@pytest.fixture(scope="module")
def bench_pool_programs(one_chip):
    """{kind: (traced, compiled)} of the five programs the benchmark's
    serving cells start with — `engine._startup_programs([512])` of the
    `bart_large_dec` pool with two of its twelve layers (the pool of
    zeros is 0.5 GB a layer on the host) — compiled for the described
    chip, arguments as the engine hands them over."""
    import json

    from benchmark.builders import paged_pool

    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmark", "configs",
                           "bart_large_dec.json")) as f:
        cfg = json.load(f)
    cfg["decoder_layers"] = 2
    eng = paged_pool.build(cfg, 1, None)
    eng._ensure_state(np.zeros(cfg["assumed"]["memory_shape"], "f4"))

    def described(x):
        x = x if hasattr(x, "dtype") else jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        # the dispatchers ask the backend, see the CPU, and would take
        # the gather composition
        mp.setattr(A, "_on_tpu", lambda: True)
        for key, build, args in eng._startup_programs([512]):
            traced = build().trace(*jax.tree_util.tree_map(described,
                                                           args))
            out[key[0]] = (traced, traced.lower().compile())
    out["pool_shape"] = eng._state["paged"][0]["k"].shape
    assert eng.trace_counts and set(eng.trace_counts.values()) == {1}
    return out


@pytest.mark.parametrize("program", BENCH_PROGRAMS)
def test_bench_pool_programs_copy_no_pool(bench_pool_programs, program):
    """The whole programs of ROADMAP S1 and S3, held without a chip: the
    decode step, the join, the prefix cache's attach, copy-on-write and
    tail prefill all update the token-major pages in place — no `copy`
    with the pool's shape, and the decode step's scratch is a few
    activations, not pools."""
    S, h, L, d, psz = (BENCH_POOL[k] for k in ("S", "h", "L", "d", "psz"))
    n_pages = S * L // psz
    assert bench_pool_programs["pool_shape"] == (n_pages + 1, psz, h * d)
    _, compiled = bench_pool_programs[program]
    assert not _pool_copies(compiled, n_pages, psz, h * d)
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    # the paged kernel once a layer where the program attends over pages
    # (pjoin's are the prompt's flash kernels)
    assert (kernels >= 2) == (program in ("pstep", "pattach", "pjoin"))
    if program == "pstep":
        assert compiled.memory_analysis().temp_size_in_bytes < \
            BENCH_PSTEP_TEMP_LIMIT


def _sub_programs(eqn):
    """The jaxprs an equation holds: a kernel body, an inner jit, the
    bodies of a loop or a branch."""
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _traced_equations(jaxpr, seen):
    """Equations a trace paid for: every equation of the program and of
    each DISTINCT sub-program it holds (kernel bodies, inner jits, loop
    bodies) — a sub-program called twelve times was traced once."""
    if id(jaxpr) in seen:
        return 0
    seen.add(id(jaxpr))
    return len(jaxpr.eqns) + sum(
        _traced_equations(sub, seen)
        for eqn in jaxpr.eqns for sub in _sub_programs(eqn))


def test_pattach_traces_no_larger_than_pjoin(bench_pool_programs):
    """Start-up cost, held where a CPU can hold it: the serving cells
    compile `pattach` and never run it, so what it costs to trace is
    pure set-up time. The tail prefill's program (write T rows, attend
    through the table) is no larger than the join's of the same 512
    tokens. (PR 28's was four times it: a Python loop over the tail's
    rows in the kernel body and one in the page write.)"""
    size = {k: _traced_equations(bench_pool_programs[k][0].jaxpr.jaxpr,
                                 set())
            for k in ("pattach", "pjoin")}
    assert size["pattach"] <= size["pjoin"], size


def test_paged_verify_traces_to_the_same_size_at_any_T():
    """The verify-mode kernel takes its T query rows as one block: the
    call traces to the same number of equations at T = 2 and T = 16 (and
    the float32 page write beside it to one scatter at any T)."""
    from paddle_tpu.serving import paging as PG

    S, h, d, psz, mp = 2, 2, 64, 16, 4
    pages = jnp.zeros((S * mp + 1, psz, h * d), jnp.float32)
    table = jnp.zeros((S, mp), jnp.int32)
    n = jnp.full((S,), 20, jnp.int32)
    bias = jnp.zeros((S, mp * psz), jnp.float32)

    def sizes(T):
        q = jnp.zeros((S, h, T, d), jnp.float32)
        kernel = jax.make_jaxpr(
            lambda q: A.paged_flash_verify(q, pages, pages, None, None,
                                           table, n, bias))(q)
        write = jax.make_jaxpr(
            lambda q: PG.write_tokens(pages, None, table, n, q)[0])(q)
        return (_traced_equations(kernel.jaxpr, set()),
                _traced_equations(write.jaxpr, set()))

    assert sizes(2) == sizes(16)


# the training cell `nemotron3_nano_ep16.pretrain_b2_s8192`: 2 sequences
# of 8,192, bfloat16; attention 32 query heads over 2 key-value heads of
# 128; the scan 64 heads of 64 in 8 groups, state 128, chunk 128; the
# experts 8 held, hidden 2688, width 1856, 6 slots a token
NEMO = dict(b=2, s=8192, hq=32, hkv=2, d=128, h=64, p=64, g=8, n=128,
            chunk=128, held=8, hid=2688, ff=1856, k=6)


def test_grouped_query_flash_compiles_at_the_training_cell_shapes(
        one_chip, monkeypatch):
    """s = 8192 through the flash kernels forward and backward, K and V
    of 2 heads repeated to the 32 query heads by the dispatcher; the
    dK/dV kernel there needs more scoped fast memory than the default."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    b, s, hq, hkv, d = (NEMO[k] for k in ("b", "s", "hq", "hkv", "d"))

    def loss(q, k, v):
        out = A.sdpa_bshd(q, k, v, is_causal=True)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.value_and_grad(loss, (0, 1, 2)), one_chip,
                        ((b, s, hq, d), jnp.bfloat16),
                        ((b, s, hkv, d), jnp.bfloat16),
                        ((b, s, hkv, d), jnp.bfloat16))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert f"bf16[{b},{s},{s}]" not in text and f"[{s},{s}]" not in text


def _compile_xla(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_chunked_scan_compiles_at_the_training_cell_shapes(one_chip):
    from paddle_tpu.ops import ssm

    b, s, h, p, g, n = (NEMO[k] for k in ("b", "s", "h", "p", "g", "n"))

    def loss(x, dt, a, bm, cm, d):
        return ssm.ssd_scan(x, dt, a, bm, cm, d,
                            chunk=NEMO["chunk"]).astype(jnp.float32).sum()

    compiled = _compile_xla(
        jax.value_and_grad(loss, range(6)), one_chip,
        ((b, s, h, p), jnp.bfloat16), ((b, s, h), jnp.float32),
        ((h,), jnp.float32), ((b, s, g, n), jnp.bfloat16),
        ((b, s, g, n), jnp.bfloat16), ((h,), jnp.float32))
    # one group of heads at a time: far under a state per position
    # (b s h p n float32 = 34 GB) and under all groups' decay matrices
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30


def test_routed_experts_compile_at_the_training_cell_shapes(one_chip):
    from paddle_tpu.ops import moe

    t = NEMO["b"] * NEMO["s"]
    held, hid, ff, k = (NEMO[x] for x in ("held", "hid", "ff", "k"))

    def loss(x, w, w_in, w_out, order, starts, counts):
        return moe.routed_experts(x, w, w_in, w_out, order, starts,
                                  counts)[0].astype(jnp.float32).sum()

    compiled = _compile_xla(
        jax.value_and_grad(loss, range(4)), one_chip,
        ((t, hid), jnp.bfloat16), ((t, k), jnp.float32),
        ((held, hid, ff), jnp.bfloat16), ((held, ff, hid), jnp.bfloat16),
        ((t * k,), jnp.int32), ((held,), jnp.int32), ((held,), jnp.int32))
    # no buffer sized for a load: the worst case (every slot on a held
    # expert) would gather t k rows of hid, 528 MB in bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30


def test_block_recomputation_lowers_the_step_scratch(one_chip):
    """`SpmdTrainer(remat=True)` recomputes block by block: on a small
    stack whose activations outweigh its parameters (eight encoder layers
    of width 64 over 16 x 256 tokens) the compiled step's scratch falls by
    more than half. (Compiled for the chip: the CPU compiler drops the
    barriers that keep a recomputation apart.)"""
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh
    from paddle_tpu.text import ErnieConfig, ErnieForSequenceClassification

    def ce(logits, labels):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(lp, labels[:, None], -1).mean()

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    temp = {}
    for remat in (False, True):
        paddle.seed(5)
        net = ErnieForSequenceClassification(ErnieConfig.tiny(
            vocab_size=64, hidden_size=64, num_layers=8, max_position=256,
            intermediate_size=128, hidden_dropout=0.0, attn_dropout=0.0))
        tr = SpmdTrainer(net, ce, fopt.sgd(0.1), remat=remat,
                         mesh=init_mesh(dp=1, devices=jax.devices()[:1]))
        tr._build_step()
        state = jax.tree_util.tree_map(
            sds, (tr.params, tr.opt_state, tr.buffers,
                  jax.random.PRNGKey(0)))
        ids = jax.ShapeDtypeStruct((16, 256), jnp.int64, sharding=one_chip)
        labels = jax.ShapeDtypeStruct((16,), jnp.int64, sharding=one_chip)
        temp[remat] = jax.jit(tr._raw_step).lower(
            *state, (ids,), labels).compile().memory_analysis() \
            .temp_size_in_bytes
    assert temp[True] < 0.5 * temp[False], temp


@pytest.mark.parametrize("m", [16, 2048])
def test_int8_matmul_kernel_compiles(one_chip, m):
    d, n = 512, 2048
    bm, bn = Q._pick_int8_blocks_heuristic(m, n)
    _compile(Q._int8_matmul_call(m, d, n, bm, bn, False), one_chip,
             ((m, d), jnp.float32), ((d, n), jnp.int8),
             ((1, n), jnp.float32))


def test_lora_gather_kernel_compiles(one_chip):
    b, s, d, r, n_out, n = 16, 1, 512, 8, 512, 4
    _compile(Q._lora_gather_call(b, s, d, r, n_out, False), one_chip,
             ((b,), jnp.int32), ((b, s, d), jnp.float32),
             ((n, d, r), jnp.float32), ((n, r, n_out), jnp.float32))


def test_kernel_failure_on_tpu_backend_raises(monkeypatch):
    """No dispatcher may swallow a kernel's failure and hand back the
    XLA reference: on a TPU backend that would hide a kernel that no
    longer lowers behind a run that "works", slower. (Needs no
    topology: the gate is steered from here and the kernel entry point
    is made to fail.)"""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)

    def boom(*a, **k):
        raise RuntimeError("mosaic refused the kernel")

    q = jnp.zeros((1, 2, 1, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 256, 64), jnp.float32)
    n = jnp.asarray([3], jnp.int32)
    monkeypatch.setattr(A, "flash_decode", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.decode_attention(q, kv, kv, n)
    monkeypatch.setattr(A, "flash_verify", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.verify_attention(jnp.zeros((1, 2, 4, 64)), kv, kv, n)
    pages = jnp.zeros((17, 16, 2 * 64), jnp.float32)
    table = jnp.asarray(np.arange(16).reshape(1, 16), jnp.int32)
    monkeypatch.setattr(A, "paged_flash_decode", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.paged_decode_attention(q, pages, pages, None, None, table, n)
    monkeypatch.setattr(A, "paged_flash_verify", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        A.paged_verify_attention(jnp.zeros((1, 2, 4, 64)), pages, pages,
                                 None, None, table, n)
    monkeypatch.setattr(Q, "_int8_matmul_call", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        Q.int8_matmul(jnp.zeros((8, 128)), jnp.zeros((128, 128), jnp.int8),
                      jnp.ones((128,)))
    monkeypatch.setattr(Q, "_lora_gather_call", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        Q.lora_delta(jnp.zeros((2, 1, 128)), jnp.zeros((3, 128, 8)),
                     jnp.zeros((3, 8, 128)), jnp.asarray([0, 1]))


# (heads, head size, page size, query rows) -> does the pool take the
# paged kernels. The gate reads shapes alone, in `_paged_kernel_fits`.
_PAGED_GATE = {
    "bench_pool": ((16, 64, 16, 1), True),
    "bench_tail": ((16, 64, 16, 16), True),
    "smoke_pool": ((8, 64, 16, 4), True),
    "lanes_off_tile": ((3, 32, 16, 1), False),      # 96 lanes
    "rows_off_tile": ((2, 64, 12, 1), False),
    "page_over_1MiB": ((16, 64, 512, 1), False),
    "rows_block_over_4MiB": ((16, 64, 16, 128), False),
}


# (page size, heads x head size, query rows, pages a slot, page dtype)
# -> the pages a grid step takes. `_paged_block_pages` reads these and
# nothing else: no tuning key, environment variable or argument.
_PAGED_BLOCK = {
    "bench_decode": ((16, 1024, 1, 64, "float32"), 8),
    "bench_verify_8_rows": ((16, 1024, 8, 64, "float32"), 8),
    "bench_tail_16_rows": ((16, 1024, 16, 64, "float32"), 4),
    "bench_tail_64_rows": ((16, 1024, 64, 64, "float32"), 1),
    "bench_int8": ((16, 1024, 1, 64, "int8"), 8),
    "smoke_pool": ((16, 512, 4, 64, "float32"), 8),
    "short_table": ((16, 1024, 1, 3, "float32"), 2),    # P <= mp
    "one_page_slots": ((16, 1024, 1, 1, "float32"), 1),
    "table_of_11": ((8, 128, 1, 11, "float32"), 8),     # not a divisor
    "wide_rows": ((32, 4096, 1, 64, "float32"), 2),     # 512 KiB a page
    "wide_rows_bf16": ((32, 4096, 1, 64, "bfloat16"), 4),
    "page_of_1MiB": ((64, 4096, 1, 64, "float32"), 1),
}


@pytest.mark.parametrize("case", sorted(_PAGED_BLOCK))
def test_paged_block_pages_reads_shapes_and_dtype_only(case):
    (psz, hd, T, mp, dtype), want = _PAGED_BLOCK[case]
    P = A._paged_block_pages(psz, hd, T, mp, dtype)
    assert P == want
    assert P & (P - 1) == 0 and 1 <= P <= min(mp, A._PAGED_BLOCK_PAGES)
    # the block of the T rows against the P pages, and a K or V block
    # as stored, within what the constants give a grid step
    assert T * P * 4 * psz * hd <= A._PAGED_BLOCK_BYTES or P == 1
    assert P * psz * hd * np.dtype(dtype).itemsize <= A._PAGED_PAGE_BYTES
    # the engine's gauge reads the same rule at one query row
    assert A.paged_decode_block_pages(psz, hd, mp, dtype) == \
        A._paged_block_pages(psz, hd, 1, mp, dtype)
    assert A.paged_decode_block_pages(12, hd, mp, dtype) == 1   # gather


@pytest.mark.parametrize("case", sorted(_PAGED_GATE))
def test_paged_dispatch_reads_the_fallback_from_shapes(monkeypatch, case):
    """One layout, one gate: on a TPU backend a pool whose rows tile and
    whose step fits VMEM takes the kernel, every other takes the XLA
    gather composition over the SAME pages — chosen from the shapes,
    never from a name or an option, and equal to the reference."""
    (h, d, psz, T), fits = _PAGED_GATE[case]
    assert A._paged_kernel_fits(psz, h * d, T) == fits
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    took = []

    def kernel(q, *a, **k):
        took.append(q.shape)
        return q

    monkeypatch.setattr(A, "paged_flash_decode", kernel)
    monkeypatch.setattr(A, "paged_flash_verify", kernel)
    # the composition's own dense kernel is not this test's business
    monkeypatch.setattr(
        A, "verify_attention",
        lambda q, k, v, n, bias=None, scale=None, split_k=None:
        A.verify_attention_reference(q, k, v, n, bias, scale))
    rs = np.random.RandomState(7)
    mp = max(2, -(-2 * T // psz))               # room for two blocks
    pages = jnp.asarray(rs.randn(2 * mp + 1, psz, h * d), jnp.float32)
    table = jnp.asarray(rs.permutation(2 * mp).reshape(2, mp), jnp.int32)
    n = jnp.asarray([T + 1, mp * psz], jnp.int32)
    q = jnp.asarray(rs.randn(2, h, T, d), jnp.float32)
    dispatch, reference = (
        (A.paged_decode_attention, A.decode_attention_reference)
        if T == 1 else
        (A.paged_verify_attention, A.verify_attention_reference))
    out = dispatch(q, pages, pages, None, None, table, n)
    assert bool(took) == fits
    if not fits:
        dense = A.paged_gather_kv(pages, None, table, h, q.dtype)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(reference(q, dense, dense, n)))


def test_partitioned_trace_takes_the_xla_composition(monkeypatch):
    """The chip's compiler refuses a Mosaic kernel in a program the SPMD
    partitioner splits ("cannot be automatically partitioned", seen when
    the sharded pool was first compiled for a v5e:2x2). That is a
    placement the code can observe, so it is a gate, not a failure: in
    a `partitioned_trace` (the mesh-sharded train step) or under
    `decode_shardings` (every program of the sharded serving pool) the
    dispatchers take the XLA composition and never touch the kernel."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)

    def boom(*a, **k):
        raise AssertionError("kernel taken in a partitioned trace")

    for name in ("flash_attention", "flash_decode", "paged_flash_decode"):
        monkeypatch.setattr(A, name, boom)
    q = jnp.full((1, 2, 1024, 64), 0.5, jnp.float32)
    q1 = jnp.zeros((1, 2, 1, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 256, 64), jnp.float32)
    pages = jnp.zeros((17, 16, 2 * 64), jnp.float32)
    table = jnp.asarray(np.arange(16).reshape(1, 16), jnp.int32)
    n = jnp.asarray([3], jnp.int32)
    assert A._flash_usable()
    for scope in (A.partitioned_trace(), A.decode_shardings({"q": None})):
        with scope:
            assert not A._flash_usable()
            assert A.sdpa(q, q, q, is_causal=True).shape == q.shape
            assert A.decode_attention(q1, kv, kv, n).shape == q1.shape
            assert A.paged_decode_attention(
                q1, pages, pages, None, None, table, n).shape == q1.shape
    with A.partitioned_trace(False):        # a one-device mesh
        assert A._flash_usable()
    assert A._flash_usable()


# ---------------------------------------------------------------------------
# PR 33: the served Phi4Flash pool's widths (1280 lanes = 10 wide heads of
# 128, four query heads a wide head, bfloat16 pages of 16 rows)
# ---------------------------------------------------------------------------

PHI = dict(S=64, h=10, d=128, psz=16, di=5120, n=16)


def test_gathered_page_read_compiles_at_the_serving_cell_widths(one_chip):
    """What a decode step's nine readers of block 17's pages run: the one
    gather of a slot's 256-page table and `dense` over its rows, at the
    widths of the serving cell. No page-table kernel is in it, and the
    logits of a slot's 40 query heads (float32, 64 x 40 x 4096) stay far
    under the gathered rows."""
    from paddle_tpu.ops import diff_attention as DA

    S, h, d, psz = (PHI[k] for k in ("S", "h", "d", "psz"))
    mp = 256
    pages = ((S * mp + 1, psz, h * d), jnp.bfloat16)

    def read(q, kp, vp, table, length):
        return DA.paged_reader(kp, vp, table, 2 * h)(q, length)

    compiled = _compile_xla(read, one_chip, ((S, 4 * h, d // 2),
                                             jnp.bfloat16), pages, pages,
                            ((S, mp), jnp.int32), ((S,), jnp.int32))
    assert "tpu_custom_call" not in compiled.as_text()
    rows = 2 * S * mp * psz * h * d * 2          # K and V, gathered
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * rows


def test_grouped_decode_kernel_compiles_at_the_serving_cell_widths(
        one_chip):
    """The same read on the chip's path: `paged_reader` takes the
    `paged_grouped_decode` kernel (32 pages a grid step), which reads
    the written pages through the table itself, so the program gathers
    no rows: its scratch is the queries and the output, not the 1.3 GB
    of gathered K and V."""
    from paddle_tpu.ops import diff_attention as DA
    from paddle_tpu.ops import grouped_decode as GD

    S, h, d, psz = (PHI[k] for k in ("S", "h", "d", "psz"))
    mp = 256
    pages = ((S * mp + 1, psz, h * d), jnp.bfloat16)
    assert GD.block_pages(psz, h * d, mp, "bfloat16") == 32
    orig = A._on_tpu
    A._on_tpu = lambda: True
    try:
        compiled = _compile(
            lambda q, kp, vp, table, length: DA.paged_reader(
                kp, vp, table, 2 * h)(q, length), one_chip,
            ((S, 4 * h, d // 2), jnp.bfloat16), pages, pages,
            ((S, mp), jnp.int32), ((S,), jnp.int32))
    finally:
        A._on_tpu = orig
    assert "paged_grouped_decode" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_selective_scan_compiles_at_the_serving_cell_shapes(one_chip):
    """The Pallas scan a join runs (one row, 2048 positions, 5120
    channels, 16 states) and the composition it replaces on the chip."""
    from paddle_tpu.ops import ssm

    di, n = PHI["di"], PHI["n"]
    shapes = (((1, 2048, di), jnp.bfloat16), ((1, 2048, di), jnp.float32),
              ((di, n), jnp.float32), ((1, 2048, n), jnp.bfloat16),
              ((1, 2048, n), jnp.bfloat16), ((di,), jnp.float32),
              ((1,), jnp.int32))

    def scan(x, dt, a, bm, cm, d, length):
        return ssm.selective_scan(x, dt, a, bm, cm, d, None, length)

    # off the chip the gate takes the composition: a chunk's states at a
    # time, not a state a position (2048 x 5120 x 16 float32 = 0.67 GB)
    assert not ssm.selective_scan_kernel_chosen(di, n)
    composed = _compile_xla(scan, one_chip, *shapes)
    assert composed.memory_analysis().temp_size_in_bytes < 0.4 * 2**30
    orig = A._on_tpu
    A._on_tpu = lambda: True
    try:
        assert ssm.selective_scan_kernel_chosen(di, n)
        assert not ssm.selective_scan_kernel_chosen(di + 64, n)
        # another function object: jit keeps the composition's trace
        compiled = _compile(lambda *a: scan(*a), one_chip, *shapes)
    finally:
        A._on_tpu = orig
    assert "selective_scan" in compiled.as_text()
    # the columns of B and C spread over the lanes, and nothing s x n x c
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 2**30


# ---------------------------------------------------------------------------

DSV3 = dict(S=64, L=6144, heads=128, hidden=7168, psz=16)


def _latent_attention():
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    # the cell's head slices and latent widths; the hidden side is cut
    # (it is not in the products compiled here)
    return nn.LatentAttention(
        256, DSV3["heads"], 64, 512, 128, 64, 128,
        rope=dict(factor=40, mscale_all_dim=1), row_pad=64,
        dtype="bfloat16")


def test_latent_decode_read_compiles_at_the_serving_cell_shapes(one_chip):
    """A decode step's read of one block's latent pages at the cell's
    sizes (64 slots x 6,144 positions, rows of 640, 128 heads): the
    gather through the table and the absorbed products. The softmax
    must not turn into a reduce-window as wide as the keys: normalising
    the weights before the value product did, and took 13.5 of a
    step's 21 ms a layer on the chip (PERF.md section 6, PR 35)."""
    attn = _latent_attention()
    S, L, psz = (DSV3[k] for k in ("S", "L", "psz"))
    mp = L // psz

    def read(q, pages, table, n_keys):
        rows = pages[table].reshape(S, -1, pages.shape[-1])
        return attn.absorbed(q, rows, n_keys)

    compiled = _compile_xla(
        read, one_chip, ((S, DSV3["heads"], 192), jnp.bfloat16),
        ((S * mp + 1, psz, attn.row_width), jnp.bfloat16),
        ((S, mp), jnp.int32), ((S,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert f"size=1x1x{2 * L - 1}" not in text
    rows = S * L * attn.row_width * 2                  # gathered once
    scores = S * DSV3["heads"] * L * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < rows + 2 * scores


def test_latent_decode_kernel_compiles_at_the_serving_cell_shapes(one_chip):
    """The same read on the chip's path: `absorbed_paged` takes the
    `paged_latent_decode` kernel (32 pages a grid step), which reads the
    pages through the table itself, so the program gathers no rows and
    holds no [slots, heads, keys] scores: its scratch is the query and
    the output, not the 0.5 GB of rows or the 0.2 GB of scores."""
    from paddle_tpu.ops import latent_decode as LD

    attn = _latent_attention()
    S, L, psz = (DSV3[k] for k in ("S", "L", "psz"))
    mp = L // psz
    assert LD.block_pages(psz, attn.row_width, mp, "bfloat16") == 32
    orig = A._on_tpu
    A._on_tpu = lambda: True
    try:
        compiled = _compile(
            lambda q, pages, table, n_keys: attn.absorbed_paged(
                q, pages, table, n_keys), one_chip,
            ((S, DSV3["heads"], 192), jnp.bfloat16),
            ((S * mp + 1, psz, attn.row_width), jnp.bfloat16),
            ((S, mp), jnp.int32), ((S,), jnp.int32))
    finally:
        A._on_tpu = orig
    text = compiled.as_text()
    assert "paged_latent_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("path", ["flash", "composed"])
def test_latent_join_attention_compiles_at_the_serving_cell_shapes(
        one_chip, path):
    """A 4,096-position join's unabsorbed attention. On the chip: the
    flash kernel over heads closed with zeros to 256 lanes (192-wide
    queries and keys, 128-wide values: the kernel has one head size).
    Elsewhere: a group of heads' scores at a time (all 128 heads' would
    be 8.6 GB in float32)."""
    attn = _latent_attention()
    s = 4096
    orig = A._on_tpu
    A._on_tpu = lambda: path == "flash"
    try:
        # another function object a path: jit keeps a trace by function
        compiled = _compile_xla(
            lambda q, rows: attn.causal(q, rows), one_chip,
            ((1, s, DSV3["heads"], 192), jnp.bfloat16),
            ((1, s, attn.row_width), jnp.bfloat16))
    finally:
        A._on_tpu = orig
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (path == "flash")
    assert f"size=1x1x{2 * s - 1}" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30
