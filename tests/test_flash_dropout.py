"""Flash-attention in-kernel dropout: dispatch plumbing (CPU). The
compiled kernels draw from the Mosaic PRNG, which has no CPU lowering, so
its statistics (same seed equal, other seed differs, unbiased outputs) are
checked on the chip by `chip_smoke.py`'s kernel phase; the numeric test
here is skipped on the CPU backend, which is the only one the suite runs
on."""
import numpy as np
import pytest

from paddle_tpu.ops import attention as A


def test_flash_plan_requires_key_for_dropout(monkeypatch):
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "_flash_usable", lambda: True)
    # dropout without a key cannot regenerate masks -> no flash
    assert A._flash_plan(1024, 1024, 64, None, 2, 4,
                         dropout_p=0.1, dropout_key=None) is A._NO_FLASH
    # with a key the plan goes through (maskless -> bias None)
    import jax

    key = jax.random.PRNGKey(0)
    assert A._flash_plan(1024, 1024, 64, None, 2, 4,
                         dropout_p=0.1, dropout_key=key) is None


def test_seed_from_key_shapes():
    import jax
    import jax.numpy as jnp

    seed = A._seed_from_key(jax.random.PRNGKey(3))
    assert seed.shape == (1,) and seed.dtype == jnp.int32
    raw = jnp.array([7, 9], jnp.uint32)
    seed2 = A._seed_from_key(raw)
    assert seed2.shape == (1,) and seed2.dtype == jnp.int32


def test_flash_dropout_needs_seed():
    import jax.numpy as jnp

    q = jnp.zeros((1, 1, 256, 64), jnp.float32)
    with pytest.raises(ValueError, match="dropout_seed"):
        A.flash_attention(q, q, q, None, True, None, dropout_p=0.5)


def test_drop_consts():
    t, inv = A._drop_consts(0.25)
    assert t == np.uint32(round(0.25 * 2 ** 32))
    np.testing.assert_allclose(float(inv), 1.0 / 0.75, rtol=1e-6)
    t1, _ = A._drop_consts(1.0 - 1e-9)
    assert int(t1) <= 2 ** 32 - 1


def test_flash_dropout_numerics_on_tpu():
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "cpu":
        pytest.skip("the Mosaic PRNG has no CPU lowering; chip_smoke.py's "
                    "kernel phase checks its statistics on the chip")

    b, h, s, d = 1, 2, 512, 64
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, h, s, d).astype("f4")) * 0.3
    k = jnp.asarray(rs.randn(b, h, s, d).astype("f4")) * 0.3
    v = jnp.asarray(rs.randn(b, h, s, d).astype("f4")) * 0.3
    gdir = jnp.asarray(rs.randn(b, h, s, d).astype("f4"))
    ref = jax.jit(lambda q, k, v: A.sdpa_reference(
        q, k, v, None, True, None))(q, k, v)
    P = 0.2
    f = jax.jit(lambda q, k, v, sd: A.flash_attention(
        q, k, v, None, True, None, dropout_p=P, dropout_seed=sd))
    outs = [np.asarray(f(q, k, v, jnp.array([i * 7 + 1], jnp.int32)))
            for i in range(40)]
    # deterministic per seed; different across seeds
    np.testing.assert_array_equal(
        outs[0], np.asarray(f(q, k, v, jnp.array([1], jnp.int32))))
    assert not np.array_equal(outs[0], outs[1])
    # unbiased: mean over seeds approaches the no-dropout reference
    m = np.mean(outs, 0)
    rel = np.abs(m - np.asarray(ref)).mean() / np.abs(np.asarray(ref)).mean()
    assert rel < 0.15, rel

    # grads: analytic P=0 flash == analytic XLA; E_seed[grad] ~ P=0 grad
    def loss(fn):
        return lambda q, k, v, sd: (fn(q, k, v, sd) * gdir).sum()

    g0 = jax.jit(jax.grad(loss(lambda q, k, v, sd: A.flash_attention(
        q, k, v, None, True, None)), (0, 1, 2)))(q, k, v, None)
    gr = jax.jit(jax.grad(loss(lambda q, k, v, sd: A.sdpa_reference(
        q, k, v, None, True, None)), (0, 1, 2)))(q, k, v, None)
    for a, b_ in zip(g0, gr):
        # f32 recompute-vs-saved-probs paths: tiny-magnitude elements
        # carry larger relative error, so pair rtol with a scale atol
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-2, atol=2e-3)
    gP = jax.jit(jax.grad(loss(lambda q, k, v, sd: A.flash_attention(
        q, k, v, None, True, None, dropout_p=P, dropout_seed=sd)),
        (0, 1, 2)))
    acc = [np.zeros_like(np.asarray(x)) for x in g0]
    N = 32
    for i in range(N):
        gs = gP(q, k, v, jnp.array([37 * i + 5], jnp.int32))
        for j in range(3):
            acc[j] += np.asarray(gs[j])
    for j in range(3):
        mj, rj = acc[j] / N, np.asarray(g0[j])
        rel = np.abs(mj - rj).mean() / (np.abs(rj).mean() + 1e-9)
        assert rel < 0.2, (j, rel)


def test_pick_blocks_divisibility_single_source_of_truth():
    """r05 review: the dispatch gate must derive from _pick_blocks so
    seqs divisible by 256/384 but not 512 still take flash."""
    assert A._pick_blocks(1024, 1024) == (512, 512)
    assert A._pick_blocks(1280, 1280) == (256, 256)
    assert A._pick_blocks(768, 768) == (384, 384)
    assert A._pick_blocks(4096, 4096) == (512, 512)
    bq, bk = A._pick_blocks(1280, 1280)
    assert 1280 % bq == 0 and 1280 % bk == 0


def test_causal_cross_shape_falls_back_to_reference():
    """r05 review: the kernels' start-aligned causal mask is WRONG for
    sq != sk (reference aligns the diagonal at the end); dispatch must
    fall back rather than return silently wrong output."""
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 2, 512, 64).astype("f4"))
    k = jnp.asarray(rs.randn(1, 2, 1024, 64).astype("f4"))
    v = jnp.asarray(rs.randn(1, 2, 1024, 64).astype("f4"))
    out = A.flash_attention(q, k, v, None, True, None)
    want = A.sdpa_reference(q, k, v, None, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="start-aligned"):
        A.flash_attention_fwd(q, k, v, None, True, None)


def test_fallback_keeps_dropout():
    """r05 review: the non-tileable/cross-shape fallback must still
    APPLY dropout (it silently dropped it before)."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(1)
    # 520 is not divisible by any supported block size
    q = jnp.asarray(rs.randn(1, 2, 520, 64).astype("f4"))
    seed = jnp.array([5], jnp.int32)
    out_p = np.asarray(A.flash_attention(
        q, q, q, None, True, None, dropout_p=0.5, dropout_seed=seed))
    out_0 = np.asarray(A.flash_attention(q, q, q, None, True, None))
    assert not np.allclose(out_p, out_0), \
        "dropout silently lost on the fallback path"
    with pytest.raises(ValueError, match="dropout_seed"):
        A.flash_attention(q, q, q, None, True, None, dropout_p=0.5)
