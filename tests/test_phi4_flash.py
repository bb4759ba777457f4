"""Phi4Flash (the SambaY decoder) at tiny widths on the CPU: each kind of
layer against its equations, the model against the plain reference
(`benchmark/reference/phi4_flash.py`), and the model SERVED, through
`ServingEngine(paged=True)` behind `ServingServer`, against the same
reference: the logits of the last prompt position and of every generated
position, through the scan state, the window rings and the pages."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu.ops import diff_attention as DA               # noqa: E402
from paddle_tpu.ops import ssm                                # noqa: E402
from paddle_tpu.serving import ServingEngine, ServingServer   # noqa: E402
from paddle_tpu.serving.scheduler import Scheduler, Request   # noqa: E402
from paddle_tpu.text.models import (Phi4FlashConfig,          # noqa: E402
                                    Phi4FlashForCausalLM)

from benchmark.reference import phi4_flash as R               # noqa: E402

#: the tiny preset as the reference reads a configuration file
REF_CFG = dict(num_hidden_layers=8, hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, sliding_window=8, layer_norm_eps=1e-5,
               assumed=dict(expand=2, d_state=4, dt_rank=4, d_conv=4))
TOL = 2e-5


@pytest.fixture(autouse=True)
def _short_scan_chunks(monkeypatch):
    """Prompts of 5 to 30 positions have to cross chunks of the scan's
    composition, as a real prompt crosses chunks of 64."""
    monkeypatch.setattr(ssm, "_SCAN_CHUNK", 4)


@pytest.fixture(scope="module")
def model():
    paddle.seed(33)
    m = Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    m.eval()
    return m


def _params(m):
    return {n: p._data for n, p in m.named_parameters()}


def _ref_logits(m, seq):
    return np.asarray(R.sequence_logits(
        _params(m), jnp.asarray(seq, jnp.int32), len(seq), REF_CFG))


# ---------------------------------------------------------------------------
# each kind of layer alone
# ---------------------------------------------------------------------------

def _scan_inputs(s=19, c=6, n=4, b=2, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.standard_normal((b, s, c)), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(r.standard_normal((b, s, c)) - 1)),
                     jnp.float32)
    a = -jnp.asarray(np.exp(r.standard_normal((c, n)) * 0.5), jnp.float32)
    bm = jnp.asarray(r.standard_normal((b, s, n)), jnp.float32)
    cm = jnp.asarray(r.standard_normal((b, s, n)), jnp.float32)
    d = jnp.asarray(r.standard_normal((c,)), jnp.float32)
    return x, dt, a, bm, cm, d


def _scan_loop(x, dt, a, bm, cm, d, upto=None):
    """The recurrence one position at a time, in numpy."""
    x, dt, a, bm, cm, d = (np.asarray(t, np.float64)
                           for t in (x, dt, a, bm, cm, d))
    b, s, c = x.shape
    h = np.zeros((b, c, a.shape[1]))
    ys = np.zeros((b, s, c))
    for t in range(s):
        live = np.ones((b,), bool) if upto is None else t < np.asarray(upto)
        new = np.exp(dt[:, t, :, None] * a) * h \
            + (dt[:, t] * x[:, t])[..., None] * bm[:, t, None, :]
        h = np.where(live[:, None, None], new, h)
        ys[:, t] = np.einsum("bcn,bn->bc", h, cm[:, t]) + d * x[:, t]
    return ys, np.swapaxes(h, 1, 2)         # held [b, n, c]


@pytest.mark.parametrize("chunk", [1, 4, 8, 32])
def test_selective_scan_against_the_position_loop(chunk, monkeypatch):
    monkeypatch.setattr(ssm, "_SCAN_CHUNK", chunk)
    args = _scan_inputs()
    y, h = ssm.selective_scan(*args)
    y0, h0 = _scan_loop(*args)
    np.testing.assert_allclose(np.asarray(y), y0, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(h), h0, atol=TOL, rtol=TOL)


def test_selective_scan_kernel_against_the_position_loop():
    """The Pallas scan (interpreted): channels a multiple of the lanes,
    200 positions (two time blocks, the tail padded), a state to start
    from, rows that stop at their own length."""
    x, dt, a, bm, cm, d = _scan_inputs(s=200, c=256, n=8, seed=3)
    length = np.asarray([150, 200])
    y0, h0 = _scan_loop(x, dt, a, bm, cm, d, upto=length)
    y, h = ssm.selective_scan(x, dt, a, bm, cm, d, None,
                              jnp.asarray(length), interpret=True)
    np.testing.assert_allclose(np.asarray(h), h0, atol=TOL, rtol=TOL)
    for row, n_valid in enumerate(length):
        np.testing.assert_allclose(np.asarray(y)[row, :n_valid],
                                   y0[row, :n_valid], atol=TOL, rtol=TOL)
    assert ssm.selective_scan_kernel_chosen(256, 8, interpret=True)
    assert not ssm.selective_scan_kernel_chosen(256, 8)      # no TPU here


def test_selective_scan_stops_feeding_the_state_at_length():
    args = _scan_inputs()
    length = np.asarray([7, 19])
    y, h = ssm.selective_scan(*args, length=jnp.asarray(length))
    y0, h0 = _scan_loop(*args, upto=length)
    np.testing.assert_allclose(np.asarray(h), h0, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(y)[0, :7], y0[0, :7], atol=TOL,
                               rtol=TOL)


def test_selective_step_against_the_scan_and_the_loop():
    x, dt, a, bm, cm, d = _scan_inputs(s=11)
    y0, h0 = _scan_loop(x, dt, a, bm, cm, d)
    # the scan over the first 6 positions leaves the state the steps
    # go on from
    _, h = ssm.selective_scan(x[:, :6], dt[:, :6], a, bm[:, :6],
                              cm[:, :6], d)
    for t in range(6, 11):
        y, h = ssm.selective_step(x[:, t], dt[:, t], a, bm[:, t],
                                  cm[:, t], d, h)
        np.testing.assert_allclose(np.asarray(y), y0[:, t], atol=TOL,
                                   rtol=TOL)
    np.testing.assert_allclose(np.asarray(h), h0, atol=TOL, rtol=TOL)


def _naive_diff(q, k, v, hkv, keep, lam, w, layer):
    """Differential attention as its equations read, a pair at a time:
    q [L, Hq, d]; k, v [L, Hkv d]; keep [L, L]."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    length, hq, d = q.shape
    k = k.reshape(length, hkv, d)
    v = v.reshape(length, hkv, d)
    li = DA.lambda_init(layer)
    out = []
    for p in range(hq // 2):
        g = p // (hq // hkv)
        vg = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)

        def soft(qh, kh):
            s = qh @ kh.T / np.sqrt(d)
            s = np.where(keep, s, -1e30)
            e = np.exp(s - s.max(-1, keepdims=True))
            return (e / e.sum(-1, keepdims=True)) @ vg

        o = soft(q[:, 2 * p], k[:, 2 * g]) \
            - lam * soft(q[:, 2 * p + 1], k[:, 2 * g + 1])
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5)
        out.append((1 - li) * o * np.asarray(w, np.float64))
    return np.concatenate(out, -1)


@pytest.mark.parametrize("window", [None, 8])
def test_differential_attention_against_its_equations(window):
    r = np.random.RandomState(2)
    length, hq, hkv, d, layer = 21, 8, 4, 8, 3
    q = jnp.asarray(r.standard_normal((1, length, hq, d)), jnp.float32)
    k = jnp.asarray(r.standard_normal((1, length, hkv * d)), jnp.float32)
    v = jnp.asarray(r.standard_normal((1, length, hkv * d)), jnp.float32)
    w = jnp.asarray(1 + 0.1 * r.standard_normal((2 * d,)), jnp.float32)
    lam = 0.37
    pos = np.arange(length)
    keep = pos[None] <= pos[:, None]
    if window:
        keep &= pos[:, None] - pos[None] < window
    want = _naive_diff(q[0], k[0], v[0], hkv, keep, lam, w, layer)
    got = DA.combine(DA.causal(q, k, v, hkv, window), lam, w, layer)[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=TOL)
    # one query against the rows a decoding slot holds: the last position
    one = DA.combine(DA.dense(
        q[:, -1], k, v, hkv, jnp.asarray([length])), lam, w, layer)[0]
    if window is None:
        np.testing.assert_allclose(np.asarray(one), want[-1], atol=TOL,
                                   rtol=TOL)


@pytest.mark.parametrize("lengths", [[1, 13, 32], [32, 8, 17]])
def test_paged_reader_against_differential_attention_over_rows(lengths):
    """The decode step's read of block 17's pages (one gather through a
    scattered table, then `dense`) against `dense` over the same keys
    laid out in order, for slots of differing lengths."""
    r = np.random.RandomState(3)
    S, hq, hkv, d, psz, mp = 3, 8, 4, 8, 8, 4
    n = S * mp
    rows_k = r.standard_normal((S, mp * psz, hkv * d)).astype(np.float32)
    rows_v = r.standard_normal((S, mp * psz, hkv * d)).astype(np.float32)
    table = r.permutation(n).reshape(S, mp)
    kp = np.zeros((n + 1, psz, hkv * d), np.float32)
    vp = np.zeros_like(kp)
    kp[table] = rows_k.reshape(S, mp, psz, -1)
    vp[table] = rows_v.reshape(S, mp, psz, -1)
    q = jnp.asarray(r.standard_normal((S, hq, d)), jnp.float32)
    length = jnp.asarray(lengths, jnp.int32)
    got = DA.paged_reader(jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(table, jnp.int32), hkv)(q, length)
    want = DA.dense(q, jnp.asarray(rows_k), jnp.asarray(rows_v), hkv,
                    length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)
    # a key past a slot's length must not show: change one, same answer
    kp2 = kp.copy()
    kp2[table[0, -1]] += 5.0
    if lengths[0] <= (mp - 1) * psz:
        again = DA.paged_reader(jnp.asarray(kp2), jnp.asarray(vp),
                                jnp.asarray(table, jnp.int32), hkv)(
                                    q, length)
        np.testing.assert_array_equal(np.asarray(again[0]),
                                      np.asarray(got[0]))


def test_gated_memory_unit_against_its_equation(model):
    blk = next(b for b in model.layers if b.kind == "gmu")
    r = np.random.RandomState(4)
    a = jnp.asarray(r.standard_normal((3, 32)), jnp.float32)
    m = jnp.asarray(r.standard_normal((3, 64)), jnp.float32)
    g = np.asarray(a) @ np.asarray(blk.mixer.in_proj._data)
    want = (np.asarray(m) * g / (1 + np.exp(-g))) \
        @ np.asarray(blk.mixer.out_proj._data)
    np.testing.assert_allclose(np.asarray(blk.mixer.mix(a, m)), want,
                               atol=TOL, rtol=TOL)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_layout_follows_the_rule(model):
    assert [b.kind for b in model.layers] == [
        "mamba", "swa", "mamba", "swa", "mamba", "full", "gmu", "xattn"]
    assert [R.kind(REF_CFG, i) for i in range(8)] == \
        [b.kind for b in model.layers]
    big = Phi4FlashConfig()
    kinds = [big.kind(i) for i in range(32)]
    assert kinds.count("mamba") == 9 and kinds.count("swa") == 8
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert kinds.count("gmu") == 7 and kinds.count("xattn") == 7


def test_forward_against_the_reference(model):
    seq = np.random.RandomState(5).randint(0, 96, (27,))
    got = np.asarray(model(seq[None].astype(np.int32))._data[0])
    np.testing.assert_allclose(got, _ref_logits(model, seq), atol=TOL,
                               rtol=TOL)
    lax = np.asarray(R.sequence_logits(
        _params(model), jnp.asarray(seq, jnp.int32), 27, REF_CFG,
        scan="lax"))
    np.testing.assert_allclose(lax, _ref_logits(model, seq), atol=TOL,
                               rtol=TOL)


def test_a_join_runs_the_stateless_blocks_for_one_position(model,
                                                           monkeypatch):
    """What `prefill` hands each block's feed-forward: every prompt
    position up to the full-attention block, whose own output and all
    that follows are computed for the last position alone (they leave no
    state: prefill linear in the prompt but for one layer)."""
    from paddle_tpu.text.models import Phi4FlashBlock

    rows = {}
    ff = Phi4FlashBlock.feed_forward

    def counting(self, h):
        rows[self.idx] = int(np.prod(h.shape[:-1]))
        return ff(self, h)

    monkeypatch.setattr(Phi4FlashBlock, "feed_forward", counting)
    ids = np.random.RandomState(4).randint(0, 96, (2, 16)).astype(np.int32)
    model.prefill(jnp.asarray(ids), jnp.asarray([11, 16], jnp.int32))
    full = [b.kind for b in model.layers].index("full")
    assert [rows[i] for i in range(8)] == \
        [2 * 16] * full + [2] * (8 - full)


def test_token_margins_read_the_reference_logits(model):
    seq = np.random.RandomState(6).randint(0, 96, (19,))
    lg = _ref_logits(model, seq)
    short, top = R.token_margins(
        _params(model), jnp.asarray(seq[None], jnp.int32),
        jnp.asarray([19]), np.zeros((1, 0), np.float32), REF_CFG)
    want = (lg.max(-1) - lg[np.arange(19), np.roll(seq, -1)]) / lg.std(-1)
    np.testing.assert_allclose(np.asarray(short[0]), want, atol=1e-4)
    assert (np.asarray(top[0]) == (lg.argmax(-1) == np.roll(seq, -1))).all()


# ---------------------------------------------------------------------------
# the model served
# ---------------------------------------------------------------------------

class _Tap:
    """Every logits row the served programs compute, with the position it
    stands at and the token it was fed: `Phi4FlashForCausalLM.prefill` and
    `.decode` wrapped with an ordered host callback."""

    def __init__(self, monkeypatch):
        self.rows = []       # (position, fed token, logits [V])
        pre, dec = Phi4FlashForCausalLM.prefill, Phi4FlashForCausalLM.decode

        def prefill(model, ids, length):
            out = pre(model, ids, length)
            last = jnp.take_along_axis(ids, (length - 1)[:, None], 1)[:, 0]
            jax.debug.callback(self._keep, length - 1, last, out[0],
                               ordered=True)
            return out

        def decode(model, tok, index, *rest, **kw):
            out = dec(model, tok, index, *rest, **kw)
            jax.debug.callback(self._keep, index, tok, out[0],
                               ordered=True)
            return out

        monkeypatch.setattr(Phi4FlashForCausalLM, "prefill", prefill)
        monkeypatch.setattr(Phi4FlashForCausalLM, "decode", decode)

    def _keep(self, pos, tok, logits):
        for p, t, lg in zip(np.asarray(pos), np.asarray(tok),
                            np.asarray(logits)):
            self.rows.append((int(p), int(t), lg))

    def worst_error(self, prompt, tokens, ref):
        """Largest |served - reference| over the last prompt position and
        every generated position but the last token's own (it is never
        fed). A position's row is the tapped one that stands there, was
        fed the sequence's token and lies nearest the reference (idle
        slots' rows are among the candidates and never the nearest)."""
        seq = np.concatenate([prompt, tokens])
        worst = 0.0
        for p in range(len(prompt) - 1, len(seq) - 1):
            cand = [lg for pos, t, lg in self.rows
                    if pos == p and t == seq[p]]
            assert cand, f"no served logits for position {p}"
            worst = max(worst, min(float(np.abs(lg - ref[p]).max())
                                   for lg in cand))
        return worst


#: (prompt length, new tokens): shorter and longer than the window of 8;
#: 13 + 30 and 20 + 28 wrap a ring five times; six requests over three
#: slots reuse every slot, and the first three join two an iteration
TRAFFIC = [(5, 20), (13, 30), (3, 10), (20, 28), (9, 25), (8, 9)]


def _requests(seed=7):
    r = np.random.RandomState(seed)
    return [(r.randint(0, 96, (p,)).astype(np.int32), n)
            for p, n in TRAFFIC]


def _engine(model, **kw):
    return ServingEngine(model, paged=True, num_slots=3, max_len=64,
                         page_size=4, **kw)


def test_served_logits_against_the_reference(model, monkeypatch):
    tap = _Tap(monkeypatch)
    eng = _engine(model)
    srv = ServingServer(eng, max_queue=16)
    reqs = [(p, srv.submit(p, None, max_new_tokens=n, eos_id=None))
            for p, n in _requests()]
    outs = [(p, np.asarray(r.future.result(timeout=600).tokens, np.int32))
            for p, r in reqs]
    srv.shutdown(drain=True, timeout=60)
    for prompt, toks in outs:
        ref = _ref_logits(model, np.concatenate([prompt, toks]))
        want = ref.argmax(-1)[len(prompt) - 1:len(prompt) + len(toks) - 1]
        assert (toks == want).all()
        assert tap.worst_error(prompt, toks, ref) < 1e-4
    snap = eng.metrics.snapshot()
    cache = snap["cache"]
    assert cache["state_resets"] == snap["joins"] == len(TRAFFIC)
    assert cache["prefill_tokens"] == sum(p for p, _ in TRAFFIC)
    assert cache["ring_wraps"] >= 2 * 5     # two ring layers, >= 5 wraps
    assert cache["bytes"]["ring"] > 0 and cache["bytes"]["recurrent"] > 0
    assert cache["bytes"]["static"] == 0
    # the steps went ahead of the last one's tokens, each program once
    pipe = snap["pipeline"]
    assert pipe["steps_ahead"] >= pipe["decode_steps"] - 3
    assert all(v == 1 for v in eng.trace_counts.values())
    eng._alloc.check()
    assert eng._alloc.pages_free == eng._alloc.n_pages


def test_enqueue_ahead_on_and_off_give_the_same_tokens(model):
    joined = []

    class Watch:
        @staticmethod
        def on_iteration(info):
            joined.append(info["joins"])

    def serve(ahead):
        eng = _engine(model, callbacks=[Watch])
        sched = Scheduler(max_queue=16)
        reqs = [Request(p, None, max_new_tokens=n, eos_id=None)
                for p, n in _requests(seed=11)]
        for r in reqs:
            sched.submit(r)
        for _ in range(400):
            if sched.depth() == 0 and eng.idle():
                break
            (eng.run_ahead if ahead else eng.run_iteration)(sched)
        assert all(r.state == "DONE" for r in reqs)
        return [list(r.tokens) for r in reqs], eng.metrics.snapshot()

    ahead, snap_a = serve(True)
    series, snap_s = serve(False)
    assert ahead == series
    assert snap_a["pipeline"]["steps_ahead"] > 0
    assert snap_s["pipeline"]["steps_ahead"] == 0
    # two joins in one iteration: six requests queued over three free slots
    assert snap_s["joins"] == len(TRAFFIC) and max(joined) == 2


def test_a_reused_slot_shows_nothing_of_its_last_request(model):
    """One slot, two requests one after the other: the second's tokens are
    those it gives alone in a fresh pool."""
    (p1, n1), (p2, n2) = _requests(seed=13)[:2]

    def serve(prompts):
        eng = ServingEngine(model, paged=True, num_slots=1, max_len=64,
                            page_size=4)
        sched = Scheduler(max_queue=4)
        reqs = [Request(p, None, max_new_tokens=n, eos_id=None)
                for p, n in prompts]
        for r in reqs:
            sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=400)
        return [list(r.tokens) for r in reqs]

    both = serve([(p1, n1), (p2, n2)])
    alone = serve([(p2, n2)])
    assert both[1] == alone[0]


def test_requests_carry_no_memory(model):
    eng = _engine(model)
    sched = Scheduler(max_queue=4)
    bad = Request(np.arange(4, dtype=np.int32), np.zeros((2, 32), "f4"),
                  max_new_tokens=2, eos_id=None)
    sched.submit(bad)
    eng.serve_until_idle(sched, max_iterations=10)
    with pytest.raises(ValueError, match="decoder without memory"):
        bad.future.result(timeout=1)
    ok = Request(np.arange(4, dtype=np.int32), np.zeros((0,), "f4"),
                 max_new_tokens=2, eos_id=None)
    sched.submit(ok)
    eng.serve_until_idle(sched, max_iterations=20)
    assert len(ok.future.result(timeout=1).tokens) == 2


@pytest.mark.parametrize("kw, said", [
    (dict(spec_k=3), "speculative decoding"),
    (dict(adapters=object()), "LoRA tenants"),
    (dict(quantize="int8"), "int8 weights"),
    (dict(prefill_chunk=8), "chunked prefill"),
    (dict(num_pages=10), "oversubscribed page pool"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_dtype="int8"), "page storage"),
    (dict(eager_fallback=True), "eager fallback"),
    (dict(paged=False), "paged=True"),
])
def test_what_a_state_rules_out_raises_by_name(model, kw, said):
    args = dict(paged=True, num_slots=3, max_len=64, page_size=4)
    args.update(kw)
    if not args["paged"]:
        del args["page_size"]       # the dense pool has no such option
    with pytest.raises(ValueError, match=said):
        ServingEngine(model, **args)


def test_the_prefix_cache_is_off_and_says_so(model):
    eng = _engine(model)
    assert eng.prefix_cache is False and eng._prefix is None
    assert eng.can_preempt(0) is False


def test_a_decoder_stack_still_needs_embed_and_project():
    from paddle_tpu import nn

    layer = nn.TransformerDecoderLayer(16, 2, 32, dropout=0.0)
    with pytest.raises(ValueError, match="embedding and its projection"):
        ServingEngine(nn.TransformerDecoder(layer, 1), paged=True)
