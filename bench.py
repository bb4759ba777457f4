"""Benchmarks for the BASELINE.md configs, single chip.

Prints ONE JSON line (the headline ERNIE-base fine-tune throughput,
config 3) to stdout; every config's result is also written to
BENCH_DETAILS.json and echoed to stderr:

  1. fluid static-graph MNIST (LeNet, whole-block XLA Executor)  imgs/s
  2. paddle.vision ResNet-50 (dygraph functionalized, bf16)      imgs/s
  3. ERNIE-base fine-tune (bf16)                                 seq/s
  5. CTR-DNN, async native PS, unique-row bf16 wire              ex/s
  +  long_context: pallas flash vs XLA attention kernel A/B      x
  +  ernie_long:   seq-1024 fine-tune, default vs flash-forced   seq/s
                   (+ a seq-4096 row, flash vs XLA, dropout on)
  +  packed_varlen: LoD-packed segment-id flash vs padded-dense
                   fine-tune at ~50% fill                        seq/s
  +  fused_optimizer: fused vs per-param opt.step() A/B (Adam +
                   global-norm clip, ~200 small tensors)         x
  +  decode_throughput: fused static-KV-cache decode scan vs
                   eager concat-cache generation loop, tokens/s  x
  4. multichip_scaling: allreduce busbw + DP weak scaling — runs
     whenever >1 device is visible (records skipped on this 1-chip
     host; validated on the 8-device CPU mesh by the test suite).

vs_baseline for the headline is measured against a provisional 300 seq/s
target — the paddlepaddle-gpu BERT-base fp16 fine-tune per-V100-chip
class the north star asks us to match (BASELINE.json has no published
numbers; see BASELINE.md).
"""
from __future__ import annotations

import contextlib
import functools
import json
import sys
import time

import numpy as np

TARGET_SEQ_PER_SEC = 300.0

#: --trace: serving benches run under a tracer session and write a
#: chrome-trace artifact per run (tools/trace_report.py / Perfetto)
_TRACE = False


@contextlib.contextmanager
def _maybe_trace(tag):
    """Wrap a serving-bench drive in a tracer session when --trace is
    set; exports chiprun_out/paddle_tpu_trace_<tag>.json under the
    checkout (the directory a chip run brings back). Yields the
    artifact path holder (path at [0] after exit) so results can record
    it."""
    holder = [None]
    if not _TRACE:
        yield holder
        return
    import os

    from paddle_tpu.core.compile_cache import checkout_path
    from paddle_tpu.profiler import trace as T

    tr = T.start_session(capacity=1 << 18)
    try:
        yield holder
    finally:
        T.end_session()
        os.makedirs(checkout_path("chiprun_out"), exist_ok=True)
        holder[0] = tr.export_chrome_trace(checkout_path(
            "chiprun_out", f"paddle_tpu_trace_{tag}.json"))
        print(f"# trace artifact: {holder[0]}", file=sys.stderr)

STEPS = 50


def _marginal_step_time(run_n, steps, lo_frac=5):
    """Per-step time via two-point marginal measurement.

    run_n(n) must execute an n-step jitted loop end-to-end (bounded by a
    host readback) and return its wall time; it is called warm. The
    marginal slope (t_hi - t_lo) / (steps - lo) cancels the fixed
    dispatch+readback cost of a call, which is not model throughput.
    Falls back to plain t/steps (conservative) when noise wins or the
    two points coincide.
    """
    lo = max(2, steps // lo_frac)
    if lo >= steps:  # degenerate: single point, single measurement
        run_n(steps)
        dt = run_n(steps) / steps
        return dt, dt, [dt]
    for n in (steps, lo):
        run_n(n)  # compile + warm this n
    # measure ADJACENT (lo, hi) pairs and take the MEDIAN of per-pair
    # slopes: pairing cancels slow drift (each pair sees nearly the
    # same fixed overhead), and the median resists the outliers that
    # bias a min-of-points estimator in EITHER direction
    slopes = []
    t_hi_best = None
    for _ in range(7):
        t_lo = run_n(lo)
        t_hi = run_n(steps)
        t_hi_best = t_hi if t_hi_best is None else min(t_hi_best, t_hi)
        if t_hi > t_lo:
            slopes.append((t_hi - t_lo) / (steps - lo))
    if not slopes:
        return t_hi_best / steps, t_hi_best / steps, [t_hi_best / steps]
    slopes.sort()
    dt = slopes[len(slopes) // 2]
    return dt, t_hi_best / steps, slopes


def _spread(per_sample_values, kind="pair_slopes"):
    """Dispersion record for per-sample throughput estimates: the
    headline is the MEDIAN (driver-reproducible), and the spread states
    how far one observed sample can land from it (VERDICT r03 weak #2:
    single-trial numbers drifted 28% run-to-run unflagged). `kind`
    keeps the record honest about sample independence: 'pair_slopes'
    are adjacent-pair marginal slopes (noise-negative pairs dropped,
    so the sample is censored and correlated); 'trials' are fully
    independent end-to-end repetitions."""
    vs = sorted(float(v) for v in per_sample_values)
    med = vs[len(vs) // 2]
    lo, hi = vs[0], vs[-1]
    return {"samples": len(vs), "kind": kind,
            "min": round(lo, 2), "max": round(hi, 2),
            "spread_pct": round(100.0 * (hi - lo) / med, 1) if med else 0.0}



def _softmax_ce(logits, labels):
    """Shared bench loss: f32 log-softmax CE over integer labels."""
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, labels[:, None], -1).mean()


def _ernie(batch=32, seq_len=128, steps=STEPS, layers=12, hidden=768, heads=12, inter=3072):
    """Config 3 headline. r04 device profile (xprof op_profile, 20-step
    warm window): matmul-bearing fusions 71.8% of device time, big
    elementwise loop fusions (layernorm/dropout/residual chains) 9.8%,
    async copy-done 9.0% (XLA memory-space copies around the step-scan
    carries), rng 2.3%, data-formatting 1.8% — ~58% MFU with no single
    recoverable hotspot left; further gains would need fused-layernorm
    kernels of marginal value."""
    import jax

    import paddle_tpu  # noqa: F401
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh
    from paddle_tpu.text import ErnieConfig, ErnieForSequenceClassification

    BATCH, SEQ_LEN = batch, seq_len
    dev = jax.devices()[0]
    mesh = init_mesh(dp=1, devices=[dev])
    cfg = ErnieConfig(vocab_size=30522, hidden_size=hidden,
                      num_layers=layers, num_heads=heads,
                      intermediate_size=inter,
                      max_position=SEQ_LEN + 2, hidden_dropout=0.1,
                      num_classes=2)
    net = ErnieForSequenceClassification(cfg)

    ce = _softmax_ce

    tr = SpmdTrainer(net, ce, fopt.adamw(5e-5), mesh=mesh,
                     compute_dtype="bfloat16")
    rs = np.random.RandomState(0)
    ids = rs.randint(1, cfg.vocab_size, (BATCH, SEQ_LEN)).astype(np.int64)
    labels = rs.randint(0, 2, (BATCH,)).astype(np.int64)
    key = jax.random.PRNGKey(0)
    ids, labels = tr.shard_batch(ids, labels)

    # one jitted multi-step lax.scan per point; the float() readback bounds
    # completion (async-dispatch runtimes under-report otherwise)
    def run_n(n):
        t0 = time.perf_counter()
        lf = float(tr.run_steps((ids,), labels, n, rng=key))
        dt = time.perf_counter() - t0
        assert lf == lf, "ERNIE produced NaN loss"
        return dt

    dt, dt_e2e, slopes = _marginal_step_time(run_n, steps)
    v = BATCH / dt
    return {"metric": "ernie_base_finetune_seq_per_sec_per_chip",
            "value": round(v, 2), "unit": "seq/s",
            "vs_baseline": round(v / TARGET_SEQ_PER_SEC, 3),
            "e2e_value": round(BATCH / dt_e2e, 2),
            "spread": _spread([BATCH / s for s in slopes]),
            "method": "two-point marginal over jitted multi-step scans "
                      "(fixed per-call dispatch cost excluded; e2e_value "
                      "keeps it included)"}


def _ernie_long(batch=8, seq_len=1024, steps=16):
    """Long-context ERNIE fine-tune (seq 1024) WITH dropout 0.1 (the
    realistic fine-tune config): the default dispatch — the pallas
    flash kernel with IN-KERNEL counter-addressed prob-dropout — vs the
    XLA fused path forced on. This is the full-model companion to the
    `long_context` kernel A/B, and the measurement that SET the
    dispatch default: the r05 kernel (512x512 blocks, diagonal-split
    causal, scale folded into the q block) wins in-model 1.22x at
    dropout 0 and ~1.56x at dropout 0.1, where the XLA path pays RNG +
    HBM for the full [B,H,S,S] prob tensor. r04's kernel lost in-model
    (0.94x) and had no dropout at all — both VERDICT r04 items.

    Also measures a seq4096 row (smaller batch, same dropout-0.1
    config): the standalone kernel numbers promise ~3.2x at 4096 but
    the in-model bench never showed it — this records what the model
    actually sees at long context (flash vs XLA-forced)."""
    import os

    def measure(force_xla, dropout, seq=seq_len, bsz=batch,
                nsteps=steps):
        import jax

        if force_xla:
            os.environ["PT_FLASH_MIN_SEQ_BSHD"] = "999999"
            os.environ["PT_FLASH_MIN_SEQ_BSHD_DROP"] = "999999"
        else:
            os.environ.pop("PT_FLASH_MIN_SEQ_BSHD", None)
            os.environ.pop("PT_FLASH_MIN_SEQ_BSHD_DROP", None)
        from paddle_tpu.optimizer import functional as fopt
        from paddle_tpu.parallel import SpmdTrainer, init_mesh
        from paddle_tpu.text import (ErnieConfig,
                                     ErnieForSequenceClassification)

        mesh = init_mesh(dp=1, devices=[jax.devices()[0]])
        cfg = ErnieConfig(vocab_size=30522, max_position=seq + 2,
                          hidden_dropout=dropout, attn_dropout=dropout,
                          num_classes=2)
        net = ErnieForSequenceClassification(cfg)

        ce = _softmax_ce

        tr = SpmdTrainer(net, ce, fopt.adamw(5e-5), mesh=mesh,
                         compute_dtype="bfloat16")
        rs = np.random.RandomState(0)
        ids = rs.randint(1, cfg.vocab_size,
                         (bsz, seq)).astype(np.int64)
        labels = rs.randint(0, 2, (bsz,)).astype(np.int64)
        key = jax.random.PRNGKey(0)
        dids, dlabels = tr.shard_batch(ids, labels)

        def run_n(n):
            t0 = time.perf_counter()
            lf = float(tr.run_steps((dids,), dlabels, n, rng=key))
            dt = time.perf_counter() - t0
            assert lf == lf, "ernie_long produced NaN loss"
            return dt

        dt, _, slopes = _marginal_step_time(run_n, nsteps, lo_frac=4)
        return bsz / dt, slopes

    saved = {k: os.environ.get(k) for k in
             ("PT_FLASH_MIN_SEQ_BSHD", "PT_FLASH_MIN_SEQ_BSHD_DROP")}
    try:
        v_default, slopes = measure(False, 0.1)   # flash, dropout on
        v_xla, _ = measure(True, 0.1)             # XLA forced
        v_def0, _ = measure(False, 0.0)           # flash, dropout off
        v_xla0, _ = measure(True, 0.0)
        # seq4096 row: dropout on, flash vs XLA-forced (batch scaled
        # down 4x so the [B,H,S,S] prob tensor of the FORCED XLA run
        # still fits HBM; seq/s stays comparable per chip)
        v4k_fl, _ = measure(False, 0.1, seq=4096, bsz=max(batch // 4, 1),
                            nsteps=max(steps // 2, 4))
        v4k_xla, _ = measure(True, 0.1, seq=4096, bsz=max(batch // 4, 1),
                             nsteps=max(steps // 2, 4))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {"metric": "ernie_long_context_seq1024_seq_per_sec_per_chip",
            "value": round(v_default, 2), "unit": "seq/s",
            "xla_forced_seq_per_sec": round(v_xla, 2),
            "flash_vs_default": round(v_default / v_xla, 3),
            "dropout_off": {"flash": round(v_def0, 2),
                            "xla": round(v_xla0, 2),
                            "ratio": round(v_def0 / v_xla0, 3)},
            "seq4096": {"flash": round(v4k_fl, 2),
                        "xla": round(v4k_xla, 2),
                        "ratio": round(v4k_fl / v4k_xla, 3),
                        "config": {"batch": max(batch // 4, 1),
                                   "seq_len": 4096, "dropout": 0.1}},
            "spread": _spread([batch / s for s in slopes]),
            "config": {"batch": batch, "seq_len": seq_len,
                       "dropout": 0.1,
                       "note": "dropout 0.1 incl. attention probs via "
                               "the IN-KERNEL flash dropout (counter-"
                               "addressed bits); default dispatch IS "
                               "the flash path since r05 (see "
                               "sdpa_bshd docstring)"},
            "method": "two-point marginal over jitted multi-step scans"}


def _packed_varlen(batch=16, max_len=1024, steps=12, hidden=768,
                   layers=12, heads=12, inter=3072):
    """Packed (LoD-native segment ids) vs padded-dense ERNIE fine-tune
    A/B at a realistic ~50% fill length mix. Both runs train the SAME
    number of sequences per step through the full base model with
    dropout 0.1; the padded run feeds [batch, max_len] rows plus a
    padding mask (the kv-bias flash path), the packed run feeds
    core/lod.pack_padded rows — several sequences back-to-back per row,
    segment ids routed to the segment-masked flash kernel whose
    block-level early-out also skips cross-segment work. The win
    compounds: ~2x fewer rows at 50% fill times the kernel's skipped
    blocks, so packed/padded should approach 2x."""
    import jax

    import paddle_tpu  # noqa: F401
    from paddle_tpu import nn
    from paddle_tpu.core.lod import pack_padded
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh
    from paddle_tpu.text import ErnieConfig, ErnieForSequenceClassification

    rs = np.random.RandomState(0)
    # ~50% fill: lengths uniform in [max_len/16, max_len], mean ~0.53
    lens = np.sort(rs.randint(max_len // 16, max_len + 1, size=batch))
    ids = np.zeros((batch, max_len), np.int64)
    mask = np.zeros((batch, max_len), np.float32)
    vocab = 30522
    for b, n in enumerate(lens):
        ids[b, :n] = rs.randint(1, vocab, n)
        mask[b, :n] = 1.0
    labels = rs.randint(0, 2, (batch,)).astype(np.int64)
    pk = pack_padded(ids, lens, row_len=max_len)

    def cfg_for(rows):
        return ErnieConfig(vocab_size=vocab, max_position=max_len + 2,
                           hidden_size=hidden, num_layers=layers,
                           num_heads=heads, intermediate_size=inter,
                           hidden_dropout=0.1, attn_dropout=0.1,
                           num_classes=2)

    class _PackedErnie(nn.Layer):
        """Positional-arg adapter: SpmdTrainer feeds net(*inputs)."""

        def __init__(self, cfg):
            super().__init__()
            self.inner = ErnieForSequenceClassification(cfg)

        def forward(self, ids, positions, segs, cls_idx):
            return self.inner(ids, position_ids=positions,
                              attn_segment_ids=segs,
                              cls_flat_index=cls_idx)

    def measure(net, inputs):
        mesh = init_mesh(dp=1, devices=[jax.devices()[0]])
        tr = SpmdTrainer(net, _softmax_ce, fopt.adamw(5e-5), mesh=mesh,
                         compute_dtype="bfloat16")
        key = jax.random.PRNGKey(0)
        data = tr.shard_batch(*inputs, labels)
        dins, dlabels = data[:-1], data[-1]

        def run_n(n):
            t0 = time.perf_counter()
            lf = float(tr.run_steps(dins, dlabels, n, rng=key))
            dt = time.perf_counter() - t0
            assert lf == lf, "packed_varlen produced NaN loss"
            return dt

        dt, _, slopes = _marginal_step_time(run_n, steps, lo_frac=4)
        return batch / dt, slopes

    ttype = np.zeros((batch, max_len), np.int64)
    v_padded, _ = measure(ErnieForSequenceClassification(cfg_for(batch)),
                          (ids, ttype, mask))
    v_packed, slopes = measure(
        _PackedErnie(cfg_for(pk.num_rows)),
        (pk.data.astype(np.int64), pk.positions.astype(np.int64),
         pk.segment_ids, pk.cls_flat_index().astype(np.int64)))
    return {"metric": "packed_varlen_seq_per_sec_per_chip",
            "value": round(v_packed, 2), "unit": "seq/s",
            "padded_seq_per_sec": round(v_padded, 2),
            "packed_vs_padded": round(v_packed / v_padded, 3),
            "spread": _spread([batch / s for s in slopes]),
            "config": {"sequences": batch, "max_len": max_len,
                       "packed_rows": pk.num_rows,
                       "fill": round(pk.fill, 3), "dropout": 0.1,
                       "note": "padded = kv-bias flash path on "
                               "[batch, max_len] rows; packed = "
                               "segment-masked flash on pack_padded "
                               "rows (block-level early-out), CLS "
                               "pooled per sequence via flat gather"},
            "method": "two-point marginal over jitted multi-step scans"}


def _hbm_profile():
    """Measure usable HBM bandwidth: a chained elementwise loop over a
    205MB bf16 tensor (reads+writes once per iteration), timed via the
    two-point marginal. Elementwise fusions are pure HBM streams, so
    bytes/time is the achievable roofline."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0)
                    .randn(128, 256, 56, 56) * 0.1, jnp.bfloat16)

    @jax.jit
    def run(x, n):
        return lax.fori_loop(
            0, n, lambda i, x: x * jnp.bfloat16(1.0000001)
            + jnp.bfloat16(1e-7), x)

    def run_n(n):
        t0 = time.perf_counter()
        float(run(x, n).ravel()[0])
        return time.perf_counter() - t0

    # median-of-pairs marginal (a min-of-2 estimator is biased under
    # asymmetric noise — see _marginal_step_time)
    dt, _, _ = _marginal_step_time(run_n, 60, lo_frac=6)
    return x.nbytes * 2 / max(dt, 1e-6)  # bytes/s


def _resnet50_min_traffic(batch):
    """Analytic lower bound on HBM bytes per training step, bf16
    activations: per conv, fwd reads the input activation and writes the
    output twice-read (once by the fused BN-stats reduce, once by the
    next layer via the normalize folded into its prologue); bwd reads
    dy + saved input for the weight grad, dy + weights for the data
    grad, writes dx, and re-reads the output for the relu mask.
    ~= 3*in + 5*out bytes per conv at 2B/elem. Stem/pool/fc + fp32
    param/momentum update traffic added explicitly."""
    # (in_c, in_hw, out_c, out_hw) with input sizes tracked explicitly —
    # channel counts collide across resolutions, so no c->hw lookup
    convs = [(3, 224, 64, 112)]                  # stem
    cfg = [(3, 64, 256, 56), (4, 128, 512, 28),
           (6, 256, 1024, 14), (3, 512, 2048, 7)]
    cin, hw_cur = 64, 56                         # after stem maxpool
    for n, cmid, cout, hw in cfg:
        for b in range(n):
            convs.append((cin, hw_cur, cmid, hw_cur))      # 1x1 reduce
            convs.append((cmid, hw_cur, cmid, hw))         # 3x3 (strides)
            convs.append((cmid, hw, cout, hw))             # 1x1 expand
            if b == 0:
                convs.append((cin, hw_cur, cout, hw))      # projection
            cin, hw_cur = cout, hw
    total = 0
    for ci, hi, co, ho in convs:
        in_b = batch * ci * hi * hi * 2
        out_b = batch * co * ho * ho * 2
        total += 3 * in_b + 5 * out_b
    total += 25.6e6 * 4 * 4                      # fp32 params+momentum r/w
    return total


def _resnet50(batch=128, img=224, steps=40):
    """The batch lives on device across timing calls: re-feeding host
    arrays per call is a harness cost, not model throughput;
    streamed-input training is the run_epoch + DevicePrefetcher path
    (tests/test_parallel.py::test_run_epoch_device_prefetch).

    r04 roofline finding: the step is HBM-BOUND, not MXU-bound — the
    device profile shows every hot fusion running at 630-660 GiB/s
    against a measured ~650 GB/s elementwise roof, with conv FLOP
    utilization ~0.1-0.2% on those fusions. MFU is the wrong lens for
    this model; roofline efficiency is reported instead. The step moves
    ~1.4x the ideal-folding traffic floor (BN's two-pass nature and
    saved-activation re-reads account for most of the excess).
    Experiments that did NOT move the needle (all measured on-chip):
    NHWC-internal convs (2787 vs 2708), full channels-last pure-jax
    model (2750), breaking the conv+BN-stats fusion (2606),
    1x1-conv-as-einsum (2036). The r04 op-profile refines the story:
    the 'convolution fusion' category is ~78% of device time because
    XLA already fuses each conv with its BN-stats reduction and the
    apply+relu+add chains into single passes — the bottleneck 1x1
    convs are themselves bandwidth-bound at these shapes (AI ~50
    FLOP/B). r05 CLOSED the question: the fused conv+BN Pallas kernel
    was built (ops/fused_conv.py, numerically exact, fwd+bwd incl.
    stats cotangents) and measured 0.18-0.88x vs XLA at every
    bottleneck shape; the 1x1-as-dot_general rewrite measured 2-4x at
    the chain level but 2200 vs 2708 imgs/s end to end (layout
    transitions). Leaf-event profiling shows every hot category within
    ~15% of its own traffic/MXU floor. XLA's compilation of this model
    is the envelope on this chip; see roofline.note."""
    import jax

    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh
    from paddle_tpu.vision.models import resnet50

    BATCH, IMG = batch, img
    mesh = init_mesh(dp=1, devices=[jax.devices()[0]])
    net = resnet50(num_classes=1000)

    ce = _softmax_ce

    tr = SpmdTrainer(net, ce, fopt.momentum(0.1, 0.9), mesh=mesh,
                     compute_dtype="bfloat16")
    rs = np.random.RandomState(0)
    imgs = rs.randn(BATCH, 3, IMG, IMG).astype(np.float32)
    labels = rs.randint(0, 1000, (BATCH,)).astype(np.int64)
    key = jax.random.PRNGKey(0)
    d_imgs, d_labels = tr.shard_batch(imgs, labels)

    def run_n(n):
        t0 = time.perf_counter()
        lf = float(tr.run_steps((d_imgs,), d_labels, n, rng=key))
        dt = time.perf_counter() - t0
        assert lf == lf, "ResNet produced NaN loss"
        return dt

    dt, dt_e2e, slopes = _marginal_step_time(run_n, steps, lo_frac=4)
    v = BATCH / dt
    hbm_bw = _hbm_profile()
    min_bytes = _resnet50_min_traffic(BATCH)
    floor_s = min_bytes / hbm_bw
    # reference class: paddlepaddle-gpu ResNet-50 fp16 ~780 imgs/s/V100
    return {"metric": "resnet50_train_imgs_per_sec_per_chip",
            "value": round(v, 2), "unit": "imgs/s",
            "vs_baseline": round(v / 780.0, 3),
            "e2e_value": round(BATCH / dt_e2e, 2),
            "spread": _spread([BATCH / s for s in slopes]),
            "roofline": {
                "hbm_bw_bytes_per_s": round(hbm_bw),
                "min_traffic_bytes_per_step": round(min_bytes),
                "hbm_floor_imgs_per_sec": round(BATCH / floor_s, 1),
                "frac_of_hbm_floor": round(v / (BATCH / floor_s), 3),
                "note": "step is HBM-bound; floor = ideal-folding "
                        "activation+grad bytes / measured ELEMENTWISE "
                        "HBM bandwidth — r05 established that floor is "
                        "MISCALIBRATED low: matmul/conv read streams "
                        "measure ~925 GB/s effective vs the 669 GB/s "
                        "elementwise roof, so frac_of_hbm_floor < 1 "
                        "does not indicate recoverable headroom. r05 "
                        "leaf-event trace (6-step window): conv "
                        "fusions ~24% (~= their MXU floor), BN stats "
                        "convert_reduce ~32% and BN-bwd "
                        "multiply_subtract ~25% — each within ~15% of "
                        "its own traffic floor for the passes exact "
                        "BN training structurally requires. The r04 "
                        "'unbuilt lever' was BUILT and measured this "
                        "round: the VMEM-persistent fused "
                        "scale+relu+matmul+stats Pallas kernel "
                        "(ops/fused_conv.py) loses 0.18-0.88x to "
                        "XLA's own dot_general fusions at every "
                        "bottleneck shape (fused_kernel_ab below), "
                        "and the 1x1-conv-as-dot_general rewrite wins "
                        "2-4x chain-level but loses end-to-end (2200 "
                        "vs 2708 imgs/s: dot/conv layout transitions) "
                        "— PT_CONV1X1_DOT stays off. Verdict: XLA's "
                        "conv+BN compilation is at the achievable "
                        "envelope on this chip; the honest ceiling is "
                        "the structural BN pass count, not a missing "
                        "kernel.",
                "fused_kernel_ab": {
                    "unit": "ms fwd+bwd, B128",
                    "shapes": {
                        "Ci256_Co64_HW3136": {"fused": 1.93,
                                              "xla": 0.54},
                        "Ci64_Co256_HW3136": {"fused": 1.42,
                                              "xla": 0.31},
                        "Ci512_Co128_HW784": {"fused": 1.06,
                                              "xla": 0.28},
                        "Ci128_Co512_HW784": {"fused": 0.70,
                                              "xla": 0.13},
                        "Ci1024_Co256_HW196": {"fused": 0.64,
                                               "xla": 0.13},
                        "Ci2048_Co512_HW49": {"fused": 1.06,
                                              "xla": 0.93}},
                    "conv1x1_as_dot_e2e_imgs_per_sec": 2200}},
            "method": "two-point marginal over jitted multi-step scans on a "
                      "device-resident batch (fixed per-call dispatch cost "
                      "excluded; e2e_value keeps it included)"}


def _mnist_static(batch=256, steps=4000):
    # LeNet steps are a fraction of a millisecond through the scan
    # path, so short scans leave the marginal noise-dominated; 4000
    # steps keep the in-jit window long against per-call jitter
    import paddle_tpu.fluid as fluid

    BATCH = batch
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[1, 28, 28], dtype="float32")
        lbl = fluid.layers.data("lbl", shape=[1], dtype="int64")
        c1 = fluid.layers.conv2d(img, 6, 5, padding=2, act="relu")
        p1 = fluid.layers.pool2d(c1, 2, "max", 2)
        c2 = fluid.layers.conv2d(p1, 16, 5, act="relu")
        p2 = fluid.layers.pool2d(c2, 2, "max", 2)
        f1 = fluid.layers.fc(p2, 120, act="relu")
        f2 = fluid.layers.fc(f1, 84, act="relu")
        logits = fluid.layers.fc(f2, 10)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    img_b = rs.randn(BATCH, 1, 28, 28).astype(np.float32)
    lbl_b = rs.randint(0, 10, (BATCH, 1)).astype(np.int64)
    # device-resident feed: re-sending the same 800KB batch every step
    # would time the host copy, not the Executor
    import jax

    feed = {"img": jax.device_put(img_b), "lbl": jax.device_put(lbl_b)}
    exe.run(main, feed, [loss])  # compile 1-step; materialize opt slots

    def run_n(n):
        # Executor.run_n: the whole n-step loop is ONE jitted lax.scan
        # dispatch, so the scan path measures the Executor itself and
        # not n per-step dispatches
        t0 = time.perf_counter()
        lv = exe.run_n(main, feed, [loss], n=n)[0]
        dt = time.perf_counter() - t0
        assert np.isfinite(lv).all()
        return dt

    dt, _, slopes = _marginal_step_time(run_n, steps)
    v = BATCH / dt
    # anchor: torch-CPU LeNet b256 Adam on this host, 8992.6 imgs/s
    # (single-thread; measured 2026-07-30, see BASELINE.md "Measured
    # anchors") — the CPUPlace-reference class for config 1
    return {"metric": "mnist_lenet_static_imgs_per_sec",
            "value": round(v, 2), "unit": "imgs/s",
            "vs_baseline": round(v / 8992.6, 3),
            "spread": _spread([BATCH / s for s in slopes])}


def _ctr_dnn_ps(batch=4096, chunks=8, merge_k=32):
    """Config 5: CTR-DNN, async native PS, K-step merged UNIQUE-row wire.

    A per-step loop pays three host-device calls per step (row H2D,
    step dispatch, grad D2H). The merged stream batches K training
    steps per transfer via MergedSparseStream (reference
    AsyncCommunicator max_merge_var_num, communicator.h:253), and
    dedups the chunk's ids
    on the pull side (unique_wire): the prefetch thread np.unique's the
    K*B*S ids, pulls only the UNIQUE rows from the pserver, and ships
    (rows[Upad,D] bf16, inv[K,B,S] int32). The jitted chunk gathers
    rows[inv[k]] per step; the grad w.r.t. the unique rows is XLA's
    transposed scatter-add, so the row MERGE runs on the chip and the
    readback is one already-merged [Upad,D] bf16 buffer. The host-side
    np.unique/np.add.at merge plane and the per-occurrence wire bytes
    are gone; the pserver RPCs also carry unique rows only. bf16 on the
    wire halves the link bytes; the pserver table stays fp32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.ps import (Communicator, MergedSparseStream,
                                           PsServer)
    from paddle_tpu.optimizer import functional as fopt

    BATCH, SLOTS, DIM, VOCAB, K = batch, 8, 16, 1_000_000, merge_k
    srv = PsServer(port=0, trainers=1, optimizer="sgd", lr=0.01)
    try:
        comm = Communicator([f"127.0.0.1:{srv.port}"], mode="async",
                            trainer_id=0)
        comm.start()
        # to_device=True: the prefetch thread issues the bf16 device_put
        # for chunk i+1 (rows + inv + labels) while the main loop
        # dispatches chunk i, so H2D never sits on the critical path
        ms = MergedSparseStream(comm, "ctr_emb", DIM, height=VOCAB,
                                wire_dtype="bfloat16", to_device=True,
                                unique_wire=True)
        rs = np.random.RandomState(0)
        params = {"w1": (rs.randn(SLOTS * DIM, 64) * 0.05).astype("f4"),
                  "b1": np.zeros(64, np.float32),
                  "w2": (rs.randn(64, 1) * 0.05).astype("f4"),
                  "b2": np.zeros(1, np.float32)}
        tx = fopt.adam(1e-3)
        opt_state = tx.init(params)

        def loss_fn(p, rows_u, inv_k, y):
            emb = rows_u[inv_k]             # [B,S,D] gather on device
            h = jnp.maximum(
                emb.astype(jnp.float32).reshape(BATCH, -1) @ p["w1"]
                + p["b1"], 0.0)
            pred = h @ p["w2"] + p["b2"]
            return ((pred - y) ** 2).mean()

        @jax.jit
        def run_chunk(p, s, rows_u, inv, ys):
            gacc0 = jnp.zeros(rows_u.shape, jnp.float32)

            def body(carry, inp):
                p, s, gacc = carry
                inv_k, y = inp
                lv, (gp, gr) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1))(p, rows_u, inv_k, y)
                p2, s2 = tx.update(p, gp, s)
                # gr is the [Upad,D] scatter-added row grad for this
                # step — the merge the host used to do with np.add.at
                return (p2, s2, gacc + gr.astype(jnp.float32)), lv
            (p, s, gacc), lvs = jax.lax.scan(body, (p, s, gacc0),
                                             (inv, ys))
            return p, s, gacc.astype(rows_u.dtype), lvs[-1]

        def make_chunk():
            ids = rs.randint(0, VOCAB, (K, BATCH, SLOTS)).astype(np.int64)
            ys = (ids.sum(-1, keepdims=True) % 2).astype(np.float32)
            return ids, ys

        ids0, ys0 = make_chunk()
        ms.prefetch(ids0, aux=ys0)
        upads = []

        def one_chunk():
            nonlocal params, opt_state
            # rows/inv/labels device-resident; uniq stays host-side for
            # the push RPC (it never needs to touch the device)
            rows, inv, uniq, ys_d = ms.get()
            upads.append(rows.shape[0])
            nxt = make_chunk()
            ms.prefetch(nxt[0], aux=nxt[1])    # overlap next pull + H2D
            params, opt_state, gacc, lv = run_chunk(params, opt_state,
                                                    rows, inv, ys_d)
            ms.push_async(uniq, gacc)       # one merged D2H + RPC push
            return lv

        try:
            float(one_chunk())              # compile + warm
            trials = []
            for _ in range(5):              # median-of-5 (r04 verdict
                                            # asked >=5): host-RPC jitter
                t0 = time.perf_counter()
                for _ in range(chunks):
                    lv = one_chunk()
                ms.drain()                  # grads actually at the PS
                float(lv)                   # bound the dispatch queue
                trials.append(BATCH * K * chunks
                              / (time.perf_counter() - t0))
            host_plane = {
                "ps_pull_s_per_chunk": round(
                    ms.pull_seconds / max(ms.chunks, 1), 3),
                "push_plane_s_per_chunk": round(
                    ms.push_seconds / max(ms.chunks, 1), 3),
                "note": "worker-thread seconds. push_plane includes the"
                        " grad readback, which BLOCKS until the scan"
                        " compute finishes (it bounds the dispatch"
                        " queue), plus the unique-row RPC push; the"
                        " host merge plane (np.unique/add.at) moved"
                        " onto the device (unique_wire) and the"
                        " widen/narrow passes moved into the C++"
                        " pserver (bf16 wire opcodes) — the trainer"
                        " host never converts dtypes anymore"}
        finally:
            ms.close()
            comm.stop()  # always reap the async send/recv threads
        v = sorted(trials)[len(trials) // 2]
        upad = int(np.median(upads))
        # anchor: torch-CPU in-process CTR-DNN (same tower/vocab, b512,
        # SparseAdam) on this host: 125337 ex/s — see BASELINE.md. The PS
        # path pays RPC + H2D/D2H on top; the anchor keeps the gap
        # honest rather than hidden.
        return {"metric": "ctr_dnn_async_ps_examples_per_sec",
                "value": round(v, 2), "unit": "ex/s",
                "vs_baseline": round(v / 125337.0, 4),
                "merge_k": K, "wire_dtype": "bfloat16",
                "unique_wire": {"upad_rows": upad,
                                "occurrences": K * BATCH * SLOTS},
                "spread": _spread(trials, kind="trials"),
                "host_plane": host_plane}
    finally:
        srv.stop()


def _long_context_attention(seqs=(1024, 2048, 4096), b=2, h=16, d=64,
                            iters=None):
    """Long-context attention A/B on the real chip: the Pallas flash
    kernel (fwd+bwd, causal) vs XLA's fused reference attention, value
    = flash speedup at the longest sequence. The blockwise kernel's
    O(S) memory is what makes ring/long-context sequence scaling viable
    at all (SURVEY long-context mandate), so the bench guards it stays
    both correct and fast."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as att

    if not att._on_tpu():
        return {"metric": "long_context_flash_attention",
                "status": "skipped: the flash kernels compile for a "
                          "TPU backend only"}
    out = {}
    speedup_last = None
    # per-seq scan lengths sized so the in-jit window is hundreds of ms:
    # per-iteration cost is 0.5-10 ms here, and a marginal slope over a
    # few ms of signal loses to per-call jitter
    iters_by_seq = {1024: 384, 2048: 128, 4096: 48}
    for S in seqs:
        n_it = iters if iters is not None else iters_by_seq.get(S, 64)
        q = jnp.asarray(
            np.random.RandomState(0).randn(b, h, S, d), jnp.bfloat16)

        def mk(fn):
            # n grad computations inside ONE jitted lax.scan, bounded by
            # a host readback, so per-call dispatch cost does not swamp
            # the kernel time
            def loss(q, k, v):
                return fn(q, k, v).astype(jnp.float32).sum()

            g = jax.grad(loss, (0, 1, 2))

            @functools.partial(jax.jit, static_argnums=3)
            def run_n(q, k, v, n):
                def body(c, _):
                    # perturb in q's OWN dtype: bf16 * f32-carry would
                    # silently promote Q to f32 and benchmark the wrong
                    # precision
                    qp = (q * (1 + c * 1e-9)).astype(q.dtype)
                    gq, gk, gv = g(qp, k, v)
                    return gq.astype(jnp.float32).mean(), None
                c, _ = jax.lax.scan(body, jnp.float32(0.0), None,
                                    length=n)
                return c

            def timed(n):
                t0 = time.perf_counter()
                r = float(run_n(q, q, q, n))
                assert r == r
                return time.perf_counter() - t0

            dt, _, _ = _marginal_step_time(timed, n_it, lo_frac=4)
            return dt

        t_flash = mk(lambda q, k, v: att.flash_attention(
            q, k, v, None, True, None))
        t_ref = mk(lambda q, k, v: att.sdpa_reference(
            q, k, v, None, True, None))
        speedup_last = t_ref / t_flash
        out[f"seq{S}"] = {"flash_ms": round(t_flash * 1e3, 2),
                          "xla_ref_ms": round(t_ref * 1e3, 2),
                          "speedup": round(speedup_last, 3)}
    return {"metric": "long_context_flash_attention",
            "value": round(speedup_last, 3), "unit": "x vs XLA ref",
            "by_seq": out,
            "config": {"batch": b, "heads": h, "head_dim": d,
                       "causal": True, "dtype": "bfloat16"}}


def _fused_optimizer(n_layers=14, hidden=128, steps=30):
    """Fused-vs-per-param optimizer step A/B: Adam + global-norm clip
    over a transformer-shaped bag of many small tensors (the
    dispatch-bound regime the fused step exists for). The per-param path
    launches ~200 jitted calls + N+1 clip reductions per step; the fused
    path is ONE donated XLA dispatch. What it measures is host dispatch
    overhead; the committed record is a CPU-backend run and says
    nothing about the chip."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer.layers import Parameter

    H = hidden
    shapes = []
    for _ in range(n_layers):  # attn qkv/out + biases, mlp, 2x ln
        shapes += [(H, H)] * 4 + [(H,)] * 4
        shapes += [(H, 4 * H), (4 * H,), (4 * H, H), (H,)]
        shapes += [(H,), (H,)]

    def run_path(fused):
        rs = np.random.RandomState(0)
        params = [Parameter((rs.randn(*s) * 0.02).astype("f4"),
                            name=f"p{i}") for i, s in enumerate(shapes)]
        grads = [Tensor(jnp.asarray(rs.randn(*s).astype("f4")))
                 for s in shapes]
        opt = paddle.optimizer.Adam(
            1e-3, parameters=params,
            grad_clip=nn.ClipGradByGlobalNorm(1.0))
        if not fused:
            opt._use_fused = False
        for p, g in zip(params, grads):
            p.grad = g

        def run_n(n):
            t0 = time.perf_counter()
            for _ in range(n):
                opt.step()
            jax.block_until_ready([p._data for p in params])
            return time.perf_counter() - t0

        run_n(2)  # compile + slot init
        dt, _, slopes = _marginal_step_time(run_n, steps)
        return 1.0 / dt, slopes

    fused_sps, fused_slopes = run_path(True)
    pp_sps, _ = run_path(False)
    return {"metric": "fused_optimizer_step",
            "n_params": len(shapes),
            "rule": "adam + ClipGradByGlobalNorm",
            "fused_steps_per_s": round(fused_sps, 1),
            "per_param_steps_per_s": round(pp_sps, 1),
            "value": round(fused_sps / pp_sps, 2),
            "unit": "x_vs_per_param",
            "spread": _spread([1.0 / s for s in fused_slopes])}


def _cold_start(d_model=32, nhead=2, layers=2, vocab=17, num_slots=4,
                max_len=32, buckets=(2, 4, 8)):
    """Cold-vs-warm engine start A/B: time-to-ready of a ServingEngine
    precompile with an EMPTY persistent AOT cache (every serving
    program traces + compiles) against a restarted engine precompiling
    from the POPULATED cache (every program deserializes — zero
    compiles). The warm side's first request is served under an armed
    retrace sentinel + tracer session: the bench ASSERTS zero compile
    spans before the first token (the PR 11 warm-start guarantee) and
    that warm ready time is strictly faster than cold. Host-side
    compile/deserialize work — backend-independent shape of the win."""
    import shutil

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.core.compile_cache import checkout_path
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.profiler import trace as T
    from paddle_tpu.serving import Request, Scheduler, ServingEngine

    def mk_engine():
        paddle.seed(0)
        layer = TransformerDecoderLayer(d_model, nhead, 2 * d_model,
                                        dropout=0.0)
        dec = TransformerDecoder(layer, layers)
        dec.eval()
        return ServingEngine(dec, nn.Embedding(vocab, d_model),
                             nn.Linear(d_model, vocab),
                             num_slots=num_slots, max_len=max_len)

    def serve_one(eng):
        sched = Scheduler(max_queue=8)
        rs = np.random.RandomState(1)
        prompt = rs.randint(2, vocab, (3,)).astype(np.int32)
        prompt[0] = 0
        r = Request(prompt, rs.randn(4, d_model).astype("f4"),
                    max_new_tokens=6, eos_id=1)
        sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=200)
        assert r.result(timeout=10).ok
        return list(r.tokens)

    # the cold side needs an EMPTY cache at a path that never moves
    cache_dir = checkout_path("_scratch", "aot_cold_start")
    shutil.rmtree(cache_dir, ignore_errors=True)
    try:
        # ---- cold start: empty cache, every program compiles ----
        eng_cold = mk_engine()
        rep_cold = eng_cold.precompile(
            (4, d_model), dtype="float32", prompt_buckets=buckets,
            cache=cache_dir)
        toks_cold = serve_one(eng_cold)
        ttft_cold = eng_cold.metrics.first_ttft_s
        assert rep_cold["compiled"] == rep_cold["programs"], rep_cold

        # ---- warm restart: same pool config, populated cache ----
        eng_warm = mk_engine()
        tr = T.start_session()
        try:
            with T.retrace_sentinel(eng_warm):
                rep_warm = eng_warm.precompile(
                    (4, d_model), dtype="float32",
                    prompt_buckets=buckets, cache=cache_dir)
                toks_warm = serve_one(eng_warm)
        finally:
            T.end_session()
        ttft_warm = eng_warm.metrics.first_ttft_s
        # the PR 11 guarantees, asserted in-bench
        assert rep_warm["warm"] == 1 and rep_warm["compiled"] == 0, \
            rep_warm
        assert tr.counters.get("compiles", 0) == 0, dict(tr.counters)
        assert sum(eng_warm.trace_counts.values()) == 0, \
            dict(eng_warm.trace_counts)
        assert toks_warm == toks_cold, (toks_warm, toks_cold)
        cold_s = rep_cold["time_to_ready_s"]
        warm_s = rep_warm["time_to_ready_s"]
        assert warm_s < cold_s, (warm_s, cold_s)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return {"metric": "cold_start_time_to_ready",
            "programs": rep_cold["programs"],
            "cold_ready_s": round(cold_s, 3),
            "warm_ready_s": round(warm_s, 3),
            "cold_first_ttft_ms": round(ttft_cold * 1e3, 2),
            "warm_first_ttft_ms": round(ttft_warm * 1e3, 2),
            "warm_zero_compiles": True,
            "value": round(cold_s / warm_s, 2),
            "unit": "x_faster_ready_warm_vs_cold"}


def _decode_throughput(points=((4, 64), (16, 64), (4, 128)),
                       d_model=128, nhead=4, ffn=256, n_layers=2,
                       vocab=512, mem_len=8, prompt_len=8):
    """Fused static-cache decode vs the eager concat-cache loop,
    tokens/s at several (batch, max_new_tokens) points. The eager side
    is the reference's cache regime — T.concat grows K/V every token,
    so every step reallocates and re-dispatches; the fused side runs
    prefill once plus ONE jitted lax.scan with StaticKVCache as carry
    (text/generation.py). Greedy outputs are asserted token-identical
    between the two paths, so the A/B can't silently diverge."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.text.generation import (DecodeEngine, bucket_size,
                                            generate_eager)

    layer = TransformerDecoderLayer(d_model, nhead, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, n_layers)
    dec.eval()
    embed = nn.Embedding(vocab, d_model)
    proj = nn.Linear(d_model, vocab)
    eng = DecodeEngine(dec, embed, proj)
    rs = np.random.RandomState(0)
    by_point = {}
    speedup_last = None
    for batch, max_new in points:
        memory = jnp.asarray(rs.randn(batch, mem_len, d_model)
                             .astype("f4"))
        prompt = np.full((batch, prompt_len), 0, np.int32)
        prompt[:, 1:] = rs.randint(2, vocab,
                                   (batch, prompt_len - 1))
        prompt = jnp.asarray(prompt)

        def run_fused():
            t0 = time.perf_counter()
            toks, lens = eng.generate(memory, prompt, bos_id=0,
                                      eos_id=1,
                                      max_new_tokens=max_new)
            jax.block_until_ready(0)  # generate returns host arrays
            return time.perf_counter() - t0, toks

        run_fused()                         # compile
        fused_samples = []
        toks_f = None
        for _ in range(5):
            dt, toks_f = run_fused()
            fused_samples.append(batch * max_new / dt)

        def run_eager():
            t0 = time.perf_counter()
            toks, _ = generate_eager(
                dec, embed, proj, memory, prompt, bos_id=0, eos_id=1,
                max_new_tokens=max_new,
                pad_prompt_to=bucket_size(prompt_len))
            return time.perf_counter() - t0, toks

        run_eager()                         # warm per-shape retraces
        dt_e, toks_e = run_eager()
        if not np.array_equal(np.asarray(toks_f), np.asarray(toks_e)):
            raise AssertionError(
                "fused static-cache greedy diverged from the eager "
                "concat-cache reference")
        fused_samples.sort()
        fused_tps = fused_samples[len(fused_samples) // 2]
        eager_tps = batch * max_new / dt_e
        speedup_last = fused_tps / eager_tps
        by_point[f"b{batch}_n{max_new}"] = {
            "fused_tok_per_s": round(fused_tps, 1),
            "eager_tok_per_s": round(eager_tps, 1),
            "speedup": round(speedup_last, 2),
            "spread": _spread(fused_samples, kind="trials")}
    spec = _spec_decode_ab(dec, embed, proj, d_model=d_model,
                           vocab=vocab)
    return {"metric": "decode_throughput",
            "value": round(speedup_last, 2),
            "unit": "x vs eager concat-cache loop",
            "by_point": by_point,
            "speculative": spec,
            "config": {"layers": n_layers, "d_model": d_model,
                       "nhead": nhead, "vocab": vocab,
                       "prompt_len": prompt_len, "greedy": True,
                       "parity_checked": True}}


def _spec_decode_ab(dec, embed, proj, *, d_model, vocab, spec_k=8,
                    ngram=2, max_new=96, pairs=5):
    """Speculative-decoding A/B over the serving engine's per-step
    dispatch path — the regime the feature targets: at batch 1-8 each
    decode step is one host dispatch whose overhead dominates this
    box's tiny-model compute, and draft-verify turns one-dispatch-per-
    token into two dispatches per accepted run. Workload: a
    repetitive-suffix prompt (the self-speculation sweet spot —
    templated text / copy-through); tokens asserted BIT-IDENTICAL to
    the non-spec engine per request. PAIRED per-pair ratio, alternating
    order inside pairs, median-of-pairs (the repo's 1-core noise
    discipline). The fused whole-scan DecodeEngine spec path is
    measured by tools/op_bench.py spec_decode_* rows instead (on this
    compute-bound CPU the k-wide verify pays ~k, so the fused-scan win
    only appears on bandwidth-bound hardware)."""
    import jax  # noqa: F401  (engine imports lazily)

    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.scheduler import Request, Scheduler

    def mk_engine(with_spec, slots):
        kw = dict(spec_k=spec_k, spec_ngram=ngram) if with_spec else {}
        return ServingEngine(dec, embed, proj, num_slots=slots,
                             max_len=160, **kw)

    def serve(eng, prompt, n_req):
        mem = np.random.RandomState(9).randn(8, d_model).astype("f4")
        sched = Scheduler(max_queue=32)
        reqs = [Request(prompt.copy(), mem, max_new_tokens=max_new,
                        eos_id=1) for _ in range(n_req)]
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        eng.serve_until_idle(sched)
        dt = time.perf_counter() - t0
        toks = [list(r.result(timeout=5).tokens) for r in reqs]
        return sum(len(t) for t in toks) / dt, toks

    # copy-through prompt: seed the model with a repeated pattern, then
    # use its OWN greedy continuation as the served prompt — the
    # continuation keeps following the attractor it is already on, the
    # canonical self-speculation-friendly (templated/copy-through)
    # regime
    rs = np.random.RandomState(3)
    seed_prompt = np.zeros((8,), np.int32)
    seed_prompt[1:] = np.tile(rs.randint(2, vocab, (4,)), 2)[:7]
    seeder = mk_engine(False, 1)
    _, seed_toks = serve(seeder, seed_prompt, 1)
    prompt0 = np.zeros((33,), np.int32)
    prompt0[1:] = seed_toks[0][:32]

    out = {}
    for batch in (1, 8):
        base = mk_engine(False, batch)
        spec = mk_engine(True, batch)
        serve(base, prompt0, batch)           # compile both paths
        serve(spec, prompt0, batch)
        ratios, spec_tps_s, base_tps_s = [], [], []
        toks_b = toks_s = None
        for i in range(pairs):
            order = (base, spec) if i % 2 == 0 else (spec, base)
            a_tps, a_toks = serve(order[0], prompt0, batch)
            b_tps, b_toks = serve(order[1], prompt0, batch)
            if order[0] is base:
                bt, st_, btk, stk = a_tps, b_tps, a_toks, b_toks
            else:
                bt, st_, btk, stk = b_tps, a_tps, b_toks, a_toks
            ratios.append(st_ / bt)
            spec_tps_s.append(st_)
            base_tps_s.append(bt)
            toks_b, toks_s = btk, stk
        if toks_b != toks_s:
            raise AssertionError(
                "speculative serving decode diverged from the "
                "non-spec engine (greedy acceptance must be "
                "bit-exact)")
        ratios.sort()
        med = ratios[len(ratios) // 2]
        snap = spec.metrics.snapshot()["speculation"]
        out[f"b{batch}"] = {
            "spec_tok_per_s": round(sorted(spec_tps_s)[pairs // 2], 1),
            "base_tok_per_s": round(sorted(base_tps_s)[pairs // 2], 1),
            "speedup": round(med, 2),
            "acceptance_rate": snap["acceptance_rate"],
            "draft_step_ms_p50": snap["draft_step_ms"].get("p50"),
            "verify_step_ms_p50": snap["verify_step_ms"].get("p50"),
            "spread": _spread(ratios, kind="pairs")}
    if out["b1"]["speedup"] < 1.5:
        raise AssertionError(
            f"speculative decode A/B below the 1.5x floor at batch 1: "
            f"{out['b1']}")
    return dict(out, spec_k=spec_k, ngram=ngram, max_new=max_new,
                bit_match_asserted=True,
                workload="copy-through prompt (the model's own "
                         "continuation), serving slot pool")


def _model_param_bytes(*nets):
    """Analytic weight bytes: every parameter's size x itemsize,
    straight off the Layer API (independent of the engines' ledger)."""
    total = 0
    for net in nets:
        for p in net.parameters():
            total += int(np.prod(p.shape)) * 4
    return total


def _expected_dense_pool_bytes(dec, *, num_slots, max_len, mem_len,
                               d_model, itemsize=4):
    """Closed-form dense slot-pool footprint: per layer the [S, H, L,
    D] K+V incremental caches + int32 write index and the [S, Hc, M,
    Dc] cross-attention K+V, plus the pooled tok/bias/memory rows."""
    S, L, M = num_slots, max_len, mem_len
    total = 4 * S + 4 * S * L + itemsize * S * M * d_model
    for layer in dec.layers:
        h, dh = layer.self_attn.num_heads, layer.self_attn.head_dim
        total += 2 * S * h * L * dh * itemsize + 4 * S
        hc, dc = layer.cross_attn.num_heads, layer.cross_attn.head_dim
        total += 2 * S * hc * M * dc * itemsize
    return total


def _expected_paged_pool_bytes(dec, *, num_slots, max_len, mem_len,
                               d_model, page_size, num_pages,
                               kv_dtype=None, itemsize=4):
    """Closed-form paged pool footprint: per layer the [P+1, H, page,
    D] K+V page arrays in the storage dtype (+ per-(page, head) f32
    scales when quantized) and the [S, Hc, M, Dc] cross K+V, plus
    tok/bias/memory rows and the int32 page table."""
    from paddle_tpu.serving.paging import resolve_kv_dtype

    import jax.numpy as jnp

    S, L, M = num_slots, max_len, mem_len
    max_pages = L // page_size
    total = 4 * S + 4 * S * L + itemsize * S * M * d_model
    total += S * max_pages * 4                    # device page table
    storage, quantized = resolve_kv_dtype(kv_dtype, jnp.float32)
    st_item = jnp.dtype(storage).itemsize
    for layer in dec.layers:
        h, dh = layer.self_attn.num_heads, layer.self_attn.head_dim
        total += 2 * (num_pages + 1) * h * page_size * dh * st_item
        if quantized:
            total += 2 * (num_pages + 1) * h * 4  # [P+1, 1, H] f32
        hc, dc = layer.cross_attn.num_heads, layer.cross_attn.head_dim
        total += 2 * S * hc * M * dc * itemsize
    return total


def _serving_throughput(n_requests=48, num_slots=8, d_model=128,
                        nhead=4, ffn=256, n_layers=2, vocab=512,
                        mem_len=8, max_new=12, prompt_max=8):
    """Continuous batching vs static-batch drain under Poisson
    arrivals. A side: the serving runtime — requests join the 8-slot
    ServingEngine the iteration a slot frees, so TTFT is one prefill
    away and short requests never wait on long co-residents. B side:
    the legacy regime — arrivals accumulate while DecodeEngine.generate
    drains the current batch; everyone in a batch waits for the whole
    batch (tokens only surface at the end), and nobody joins mid-run.
    Same model, same arrival schedule, same per-request work; reports
    tok/s plus p50/p99 TTFT for both."""
    import jax.numpy as jnp

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving import Request, Scheduler, ServingEngine
    from paddle_tpu.text.generation import DecodeEngine

    layer = TransformerDecoderLayer(d_model, nhead, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, n_layers)
    dec.eval()
    embed = nn.Embedding(vocab, d_model)
    proj = nn.Linear(d_model, vocab)
    rs = np.random.RandomState(0)

    def mk_workload():
        """(prompt [P], lengths, memory) per request; prompts ragged,
        right-padded copies for the static side (fixed P0=prompt_max
        so the static engine compiles one prompt bucket)."""
        work = []
        for _ in range(n_requests):
            P = int(rs.randint(1, prompt_max + 1))
            prompt = rs.randint(2, vocab, (prompt_max,)).astype("i4")
            prompt[0] = 0
            mem = rs.randn(mem_len, d_model).astype("f4")
            work.append((prompt, P, mem))
        return work

    work = mk_workload()
    max_len = bucket_sz = 1 << (prompt_max - 1).bit_length()
    max_len = bucket_sz + max_new

    # ---- A: continuous batching (synchronous drive, real clock) ----
    eng = ServingEngine(dec, embed, proj, num_slots=num_slots,
                        max_len=max_len)
    sched = Scheduler(max_queue=n_requests + 8)
    # warm every join bucket + the step before timing
    for P in sorted({1 << (max(p, 1) - 1).bit_length()
                     for _, p, _ in work}):
        r = Request(work[0][0][:P].copy(), work[0][2],
                    max_new_tokens=1, eos_id=1)
        sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=50)

    gap = 0.004   # mean Poisson inter-arrival (s): ~arrival/iteration
    gaps = rs.exponential(gap, n_requests)
    reqs = []
    with _maybe_trace("serving_throughput") as trace_art:
        t0 = time.perf_counter()
        next_arrival = t0
        i = 0
        while i < len(work) or sched.depth() > 0 or eng.occupancy() > 0:
            now = time.perf_counter()
            while i < len(work) and now >= next_arrival:
                prompt, P, mem = work[i]
                reqs.append(sched.submit(Request(
                    prompt[:P].copy(), mem, max_new_tokens=max_new,
                    eos_id=1)))
                next_arrival += gaps[i]
                i += 1
            eng.run_iteration(sched)
        cont_wall = time.perf_counter() - t0
    cont_ttft = np.asarray([r.result().ttft_s for r in reqs])
    cont_tokens = sum(len(r.result().tokens) for r in reqs)

    # ---- B: static-batch drain on DecodeEngine.generate ----
    deng = DecodeEngine(dec, embed, proj)
    for b in (1, 2, 4, 8):   # warm the batch buckets the drain hits
        mems = jnp.asarray(np.stack([work[0][2]] * b))
        pr = jnp.asarray(np.stack([work[0][0]] * b))
        ln = jnp.asarray(np.full((b,), work[0][1], "i4"))
        deng.generate(mems, pr, ln, bos_id=0, eos_id=1,
                      max_new_tokens=max_new)
    t0 = time.perf_counter()
    next_arrival = t0
    arrived = []          # (arrival_time, index)
    stat_ttft = []
    stat_tokens = 0
    i = 0
    while i < len(work) or arrived:
        now = time.perf_counter()
        while i < len(work) and now >= next_arrival:
            arrived.append((next_arrival, i))
            next_arrival += gaps[i]
            i += 1
        if not arrived:
            time.sleep(max(0.0, next_arrival - now))
            continue
        batch = arrived[:num_slots]   # same concurrency as the pool
        arrived = arrived[num_slots:]
        mems = jnp.asarray(np.stack([work[j][2] for _, j in batch]))
        pr = jnp.asarray(np.stack([work[j][0] for _, j in batch]))
        ln = jnp.asarray(np.asarray([work[j][1] for _, j in batch],
                                    "i4"))
        toks, lens = deng.generate(mems, pr, ln, bos_id=0, eos_id=1,
                                   max_new_tokens=max_new)
        t_done = time.perf_counter()
        stat_tokens += int(np.asarray(lens).sum())
        stat_ttft.extend(t_done - t_arr for t_arr, _ in batch)
    stat_wall = time.perf_counter() - t0
    stat_ttft = np.asarray(stat_ttft)

    # ---- armed-overhead A/B on the decode step ----
    # A steady pool (4 resident requests, no joins, no finishes) runs
    # pure decode iterations in alternating groups with the FULL
    # observability stack OFF and ON — tracer session + cost-accounting
    # session (MFU/goodput gauges) + HBM-ledger budget; identical
    # compiled work either way, so the medians isolate the
    # instrumentation's own cost. Asserted: armed stays within 2% of
    # disarmed — the accounting layer must be deployable always-on.
    from paddle_tpu.profiler import costs as C
    from paddle_tpu.profiler import trace as T

    ov_eng = ServingEngine(dec, embed, proj, num_slots=4, max_len=516,
                           hbm_budget_bytes=1 << 30)
    ov_sched = Scheduler(max_queue=8)
    for k in range(4):
        ov_sched.submit(Request(work[k][0][:2].copy(), work[k][2],
                                max_new_tokens=512, eos_id=None))
    for _ in range(8):                 # join all four + warm the step
        ov_eng.run_iteration(ov_sched)
    ov_book = C.CostBook()  # reused across armed steps: steady state

    def _one(tracer):
        if tracer is not None:
            T.start_session(tracer=tracer)
            C.start_accounting(book=ov_book)
        s0 = time.perf_counter()
        ov_eng.run_iteration(ov_sched)
        dt = time.perf_counter() - s0
        if tracer is not None:
            C.end_accounting()
            T.end_session()
        return dt

    # PAIRED per-step measurement: each (off, on) pair runs back to
    # back — the median of per-pair differences cancels the 1-core
    # box's drift (cpu freq, gc, scheduler) that group medians cannot
    tr = T.Tracer(capacity=1 << 15)
    off_s, diff_s = [], []
    for k in range(200):
        if k % 2 == 0:                 # alternate order inside pairs
            off = _one(None)
            on = _one(tr)
        else:
            on = _one(tr)
            off = _one(None)
        off_s.append(off)
        diff_s.append(on - off)
    off_ms = float(np.median(off_s)) * 1e3
    diff_ms = float(np.median(diff_s)) * 1e3
    on_ms = off_ms + diff_ms
    overhead_pct = diff_ms / off_ms * 100.0
    assert overhead_pct < 2.0, \
        f"armed accounting+tracing overhead {overhead_pct:.2f}% >= " \
        f"2% (on {on_ms:.3f}ms vs off {off_ms:.3f}ms per decode step)"
    ov_eng.abort_active("shutdown")

    # ---- HBM-ledger exactness (dense pool) ----
    # the snapshot's memory section must equal the ANALYTIC pool+weight
    # footprint, computed here from the model/pool config alone
    snap_mem = eng.metrics.snapshot()["memory"]
    exp = _expected_dense_pool_bytes(
        dec, num_slots=num_slots, max_len=max_len, mem_len=mem_len,
        d_model=d_model, itemsize=4)
    exp_w = _model_param_bytes(dec, embed, proj)
    assert snap_mem["total_bytes"] == exp + exp_w, \
        f"ledger {snap_mem['total_bytes']} != analytic " \
        f"{exp + exp_w} (pool {exp} + weights {exp_w})"

    def pct(a, q):
        return round(float(np.percentile(a, q)) * 1e3, 1)

    cont_tps = cont_tokens / cont_wall
    stat_tps = stat_tokens / stat_wall
    return {"metric": "serving_throughput",
            "value": round(float(np.percentile(stat_ttft, 50) /
                                 np.percentile(cont_ttft, 50)), 2),
            "unit": "x lower p50 TTFT vs static-batch drain",
            "continuous": {"tok_per_s": round(cont_tps, 1),
                           "ttft_p50_ms": pct(cont_ttft, 50),
                           "ttft_p99_ms": pct(cont_ttft, 99),
                           "wall_s": round(cont_wall, 2)},
            "static_drain": {"tok_per_s": round(stat_tps, 1),
                             "ttft_p50_ms": pct(stat_ttft, 50),
                             "ttft_p99_ms": pct(stat_ttft, 99),
                             "wall_s": round(stat_wall, 2)},
            "trace_overhead": {
                "armed": "tracer+costs+ledger",
                "off_step_ms": round(off_ms, 3),
                "on_step_ms": round(on_ms, 3),
                "overhead_pct": round(overhead_pct, 2),
                "asserted_lt_pct": 2.0,
                "steps_per_side": len(off_s)},
            "memory_ledger": {
                "total_bytes": snap_mem["total_bytes"],
                "analytic_bytes": exp + exp_w,
                "exact_match": True},
            **({} if trace_art[0] is None
               else {"trace_artifact": trace_art[0]}),
            "config": {"n_requests": n_requests, "slots": num_slots,
                       "layers": n_layers, "d_model": d_model,
                       "max_new_tokens": max_new,
                       "poisson_mean_gap_ms": 4,
                       "prompt_len": f"1..{prompt_max} ragged"}}


def _serving_paged(n_requests=40, d_model=64, nhead=2, ffn=128,
                   n_layers=2, vocab=128, mem_len=4, max_len=128,
                   page_size=16, dense_slots=4, prompt_max=8,
                   shared_frac=0.8):
    """Paged vs dense KV pool at EQUAL cache-memory budget. Both pools
    get the same HBM: the dense side spends it on `dense_slots` rows of
    worst-case `max_len` positions; the paged side turns the identical
    byte budget into `dense_slots * max_len / page_size` pages and lets
    slots map only what they actually use — with ragged requests (mean
    live length <= max_len / 4) that sustains several times the
    concurrency, and 80% of requests sharing one system prompt ride the
    prefix cache with zero re-prefill. Everything is submitted up
    front, so p50 TTFT measures queue wait at each pool's real
    capacity. fp32 pages: the bench ASSERTS the paged tokens bit-match
    the dense pool per request, the paged pool's peak concurrency is
    >= 2x the dense pool's, and the allocator free list returns to its
    initial state after the drain (no page leaks)."""
    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving import Request, Scheduler, ServingEngine

    layer = TransformerDecoderLayer(d_model, nhead, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, n_layers)
    dec.eval()
    embed = nn.Embedding(vocab, d_model)
    proj = nn.Linear(d_model, vocab)
    rs = np.random.RandomState(0)

    # equal-HBM sizing: positions_budget = dense_slots * max_len
    num_pages = dense_slots * max_len // page_size
    paged_slots = 4 * dense_slots     # capacity now bounded by pages,
    #                                   not rows — give it headroom
    sys_prompt = rs.randint(2, vocab, (prompt_max,)).astype("i4")
    sys_prompt[0] = 0
    sys_mem = rs.randn(mem_len, d_model).astype("f4")
    work = []
    for i in range(n_requests):
        n_new = int(rs.randint(4, 25))     # ragged: mean live length
        #                                    ~22 <= max_len / 4
        if rs.rand() < shared_frac:
            work.append((sys_prompt.copy(), sys_mem, n_new))
        else:
            P = int(rs.randint(1, prompt_max + 1))
            p = rs.randint(2, vocab, (P,)).astype("i4")
            p[0] = 0
            work.append((p, rs.randn(mem_len, d_model).astype("f4"),
                         n_new))

    def drive(eng):
        sched = Scheduler(max_queue=n_requests + 8)
        # warm every join bucket + the step outside the timed window
        for P in sorted({1 << (max(p.shape[0], 1) - 1).bit_length()
                         for p, _, _ in work}):
            r = Request(work[0][0][:P].copy(), work[0][1],
                        max_new_tokens=1, eos_id=1)
            sched.submit(r)
            eng.serve_until_idle(sched, max_iterations=200)
        if hasattr(eng, "flush_prefix_cache"):
            eng.flush_prefix_cache()   # warmup must not seed the cache
        peak = [0]

        class _Occ:
            def on_iteration(self, stats):
                peak[0] = max(peak[0], stats["occupancy"])
        eng._cbs.append(_Occ())
        reqs = []
        t0 = time.perf_counter()
        for p, m, n_new in work:
            reqs.append(sched.submit(Request(
                p.copy(), m, max_new_tokens=n_new, eos_id=1)))
        eng.serve_until_idle(sched, max_iterations=20000)
        wall = time.perf_counter() - t0
        res = [r.result() for r in reqs]
        assert all(r.ok for r in res), \
            [r.finish_reason for r in res if not r.ok]
        ttft = np.asarray([r.ttft_s for r in res])
        toks = sum(len(r.tokens) for r in res)
        return res, ttft, toks, wall, peak[0]

    dense = ServingEngine(dec, embed, proj, num_slots=dense_slots,
                          max_len=max_len, max_joins_per_iter=4)
    d_res, d_ttft, d_toks, d_wall, d_peak = drive(dense)

    paged = ServingEngine(dec, embed, proj, num_slots=paged_slots,
                          max_len=max_len, paged=True,
                          page_size=page_size, num_pages=num_pages,
                          max_joins_per_iter=4)
    with _maybe_trace("serving_paged") as trace_art:
        p_res, p_ttft, p_toks, p_wall, p_peak = drive(paged)

    # fp32 pages: bit-identical tokens to the dense pool, per request
    for a, b in zip(d_res, p_res):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # acceptance: >= 2x concurrent requests at equal cache memory
    assert p_peak >= 2 * d_peak, (p_peak, d_peak)
    # the shared system prompt rode the prefix cache (zero re-prefill):
    # only the distinct (prompt, memory) combos ever ran a prefill
    pm = paged.metrics
    assert pm.prefix_hits / max(1, pm.prefix_hits + pm.prefix_misses) \
        >= shared_frac - 0.1
    # no page leaks after the drain
    paged.flush_prefix_cache()
    paged._alloc.check()
    assert paged._alloc.pages_free == paged.num_pages
    full = paged.metrics.snapshot()
    snap = full["paging"]
    # HBM-ledger exactness (paged pool): snapshot vs the closed-form
    # page/scale/table footprint + the Layer-API weight bytes
    exp_pool = _expected_paged_pool_bytes(
        dec, num_slots=paged_slots, max_len=paged.max_len,
        mem_len=mem_len, d_model=d_model, page_size=page_size,
        num_pages=num_pages)
    exp_w = _model_param_bytes(dec, embed, proj)
    assert full["memory"]["total_bytes"] == exp_pool + exp_w, \
        (full["memory"], exp_pool, exp_w)

    def pct(a, q):
        return round(float(np.percentile(a, q)) * 1e3, 1)

    return {"metric": "serving_paged",
            "value": round(p_peak / max(1, d_peak), 2),
            "unit": "x peak concurrent requests vs dense pool at "
                    "equal cache memory",
            "bitmatch_dense": True,
            "memory_ledger": {
                "total_bytes": full["memory"]["total_bytes"],
                "analytic_bytes": exp_pool + exp_w,
                "exact_match": True},
            **({} if trace_art[0] is None
               else {"trace_artifact": trace_art[0]}),
            "paged": {"peak_concurrency": p_peak,
                      "ttft_p50_ms": pct(p_ttft, 50),
                      "ttft_p99_ms": pct(p_ttft, 99),
                      "tok_per_s": round(p_toks / p_wall, 1),
                      "prefix_hit_rate": snap["prefix_hit_rate"],
                      "wall_s": round(p_wall, 2)},
            "dense": {"peak_concurrency": d_peak,
                      "ttft_p50_ms": pct(d_ttft, 50),
                      "ttft_p99_ms": pct(d_ttft, 99),
                      "tok_per_s": round(d_toks / d_wall, 1),
                      "wall_s": round(d_wall, 2)},
            "config": {"n_requests": n_requests,
                       "cache_positions_budget": dense_slots * max_len,
                       "dense_slots": dense_slots,
                       "paged_slots": paged_slots,
                       "num_pages": num_pages, "page_size": page_size,
                       "max_len": max_len,
                       "shared_system_prompt_frac": shared_frac,
                       "max_new_tokens": "4..24 ragged (mean ~14)"}}


def _serving_paged_spec(d_model=128, nhead=4, ffn=256, n_layers=2,
                        vocab=512, mem_len=8, max_len=160,
                        page_size=16, spec_k=8, ngram=2, max_new=96,
                        pairs=5):
    """Speculative decoding ON THE PAGED POOL: paged+spec vs
    paged-plain at EQUAL cache memory (identical page pool both
    sides), batch 1 and 8, copy-through workload — the regime where
    draft-verify turns one-dispatch-per-token into two dispatches per
    accepted run while the block table keeps live bytes tracking
    actual tokens. Tokens are asserted BIT-IDENTICAL between the two
    paged engines per request, both pools drain leak-free (allocator
    free list back to initial), and the batch-1 acceptance rate must
    clear a floor (the workload is the self-speculation sweet spot —
    a collapsed acceptance means the paged verify path broke).
    PAIRED per-pair ratio, alternating order inside pairs,
    median-of-pairs (the repo's 1-core noise discipline)."""
    import jax  # noqa: F401  (engine imports lazily)

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.paging import pages_for
    from paddle_tpu.serving.scheduler import Request, Scheduler

    layer = TransformerDecoderLayer(d_model, nhead, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, n_layers)
    dec.eval()
    embed = nn.Embedding(vocab, d_model)
    proj = nn.Linear(d_model, vocab)

    # equal cache memory: BOTH pools get the same page pool, sized so
    # one slot can hold prompt + budget + the spec overhang
    pages_per_slot = pages_for(max_len + spec_k, page_size)

    def mk_engine(with_spec, slots):
        kw = dict(spec_k=spec_k, spec_ngram=ngram) if with_spec else {}
        return ServingEngine(dec, embed, proj, num_slots=slots,
                             max_len=max_len, paged=True,
                             page_size=page_size,
                             num_pages=slots * pages_per_slot, **kw)

    def serve(eng, prompt, n_req):
        mem = np.random.RandomState(9).randn(
            mem_len, d_model).astype("f4")
        sched = Scheduler(max_queue=32)
        reqs = [Request(prompt.copy(), mem, max_new_tokens=max_new,
                        eos_id=1) for _ in range(n_req)]
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        eng.serve_until_idle(sched)
        dt = time.perf_counter() - t0
        toks = [list(r.result(timeout=5).tokens) for r in reqs]
        return sum(len(t) for t in toks) / dt, toks

    # copy-through prompt: the model's own greedy continuation (see
    # decode_throughput.speculative) — templated/copy-through regime
    rs = np.random.RandomState(3)
    seed_prompt = np.zeros((8,), np.int32)
    seed_prompt[1:] = np.tile(rs.randint(2, vocab, (4,)), 2)[:7]
    seeder = mk_engine(False, 1)
    _, seed_toks = serve(seeder, seed_prompt, 1)
    prompt0 = np.zeros((33,), np.int32)
    prompt0[1:] = seed_toks[0][:32]

    out = {}
    with _maybe_trace("serving_paged_spec") as trace_art:
        for batch in (1, 8):
            base = mk_engine(False, batch)
            spec = mk_engine(True, batch)
            serve(base, prompt0, batch)       # compile both paths
            serve(spec, prompt0, batch)
            ratios, spec_tps_s, base_tps_s = [], [], []
            toks_b = toks_s = None
            for i in range(pairs):
                order = (base, spec) if i % 2 == 0 else (spec, base)
                a_tps, a_toks = serve(order[0], prompt0, batch)
                b_tps, b_toks = serve(order[1], prompt0, batch)
                if order[0] is base:
                    bt, st_, btk, stk = a_tps, b_tps, a_toks, b_toks
                else:
                    bt, st_, btk, stk = b_tps, a_tps, b_toks, a_toks
                ratios.append(st_ / bt)
                spec_tps_s.append(st_)
                base_tps_s.append(bt)
                toks_b, toks_s = btk, stk
            if toks_b != toks_s:
                raise AssertionError(
                    "paged speculative decode diverged from the "
                    "paged non-spec engine (greedy acceptance must "
                    "be bit-exact)")
            for eng in (base, spec):          # no page leaks
                eng.flush_prefix_cache()
                eng._alloc.check()
                assert eng._alloc.pages_free == eng.num_pages, \
                    (eng._alloc.pages_free, eng.num_pages)
            ratios.sort()
            med = ratios[len(ratios) // 2]
            snap = spec.metrics.snapshot()["speculation"]
            out[f"b{batch}"] = {
                "spec_tok_per_s":
                    round(sorted(spec_tps_s)[pairs // 2], 1),
                "base_tok_per_s":
                    round(sorted(base_tps_s)[pairs // 2], 1),
                "speedup": round(med, 2),
                "acceptance_rate": snap["acceptance_rate"],
                "effective_k": snap["effective_k"],
                "k_shrink_events": snap["k_shrink_events"],
                "draft_step_ms_p50": snap["draft_step_ms"].get("p50"),
                "verify_step_ms_p50":
                    snap["verify_step_ms"].get("p50"),
                "spread": _spread(ratios, kind="pairs")}
    if out["b1"]["speedup"] < 1.3:
        raise AssertionError(
            f"paged speculative A/B below the 1.3x floor at batch 1: "
            f"{out['b1']}")
    if out["b1"]["acceptance_rate"] < 0.25:
        raise AssertionError(
            f"paged spec acceptance collapsed on the copy-through "
            f"workload: {out['b1']}")
    return {"metric": "serving_paged_spec",
            "value": out["b1"]["speedup"],
            "unit": "x tokens/s vs paged non-spec at equal cache "
                    "memory (batch 1)",
            **({} if trace_art[0] is None
               else {"trace_artifact": trace_art[0]}),
            **out,
            "bit_match_asserted": True, "leak_free_asserted": True,
            "config": {"spec_k": spec_k, "ngram": ngram,
                       "max_new": max_new, "page_size": page_size,
                       "pages_per_slot": pages_per_slot,
                       "max_len": max_len,
                       "workload": "copy-through prompt (the model's "
                                   "own continuation), paged slot "
                                   "pool"}}


def _serving_radix(n_requests=28, d_model=128, nhead=2, ffn=256,
                   n_layers=2, vocab=128, mem_len=4, max_len=160,
                   page_size=16, num_slots=8, num_pages=192,
                   pre_len=112, probe_reps=5):
    """Radix vs whole-prompt-only prefix reuse on the SAME paged pool,
    two phases. Phase 1 (batch): a branching-conversation drive —
    every prompt extends one 112-token preamble, forking at page
    depths 32/64/96 (plus a mid-page fork at 40 that exercises COW)
    with a 3-4 token divergent tail, so whole-prompt keying almost
    never hits while the radix trie serves the shared prefix as pages
    and prefills ONLY the tail through the bucketed `pattach` program.
    Asserted: radix tokens bit-match the whole-prompt side per request
    (whose forks all ran COLD full prefills), hit TOKEN ratio >= 0.5,
    no retrace across hit lengths (sentinel armed), leak-free
    allocators. Phase 2 (TTFT probes): SEQUENTIAL paired single-
    request probes per fork depth (max_new_tokens=1, so TTFT is join
    cost with no queue wait, alternating sides per rep) — asserted:
    the deepest shared-preamble depth shows a strict median TTFT win.
    Since PR 17 every join DONATES the pool
    carry (the splice is in place, no whole-pool copy per join) and
    the default mid_page="round_down" policy serves mid-page forks
    from the page boundary instead of COWing the divergent page —
    the two per-join fixed costs that used to mask the 16x
    prefill-position saving on this dispatch-bound 1-core CPU. The
    batch-phase p50s still ride along unasserted: what remains is
    dispatch count, and the fleet-scale p50 win needs a
    bandwidth-bound chip (same caveat as the serving_paged row)."""
    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving import (Request, Scheduler, ServingEngine,
                                    retrace_sentinel)

    layer = TransformerDecoderLayer(d_model, nhead, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, n_layers)
    dec.eval()
    embed = nn.Embedding(vocab, d_model)
    proj = nn.Linear(d_model, vocab)
    rs = np.random.RandomState(0)

    base = rs.randint(2, vocab, (pre_len,)).astype("i4")
    base[0] = 0
    sys_mem = rs.randn(mem_len, d_model).astype("f4")
    # forks at page boundaries (32/64/96 = 2/4/6 pages of seed) plus a
    # mid-page fork (40 — under the default round_down policy it seeds
    # from the 32-token boundary with no COW; mid_page="cow" would COW
    # the divergent page); tails of 3-4 tokens keep every partial hit
    # on ONE pattach tail bucket
    forks = [32, 64, 96, 40]
    work = []
    for i in range(n_requests):
        n_new = int(rs.randint(4, 13))
        if i % 7 == 0:                      # occasional exact repeat
            p = np.concatenate([base, [5, 9, 2]]).astype("i4")
        else:
            f = forks[int(rs.randint(len(forks)))]
            t = rs.randint(2, vocab, (int(rs.randint(3, 5)),))
            p = np.concatenate([base[:f], t]).astype("i4")
        work.append((p, n_new))

    def mk_engine():
        return ServingEngine(dec, embed, proj, num_slots=num_slots,
                             max_len=max_len, paged=True,
                             page_size=page_size, num_pages=num_pages,
                             prefix_capacity=8, max_joins_per_iter=4)

    def serve_one(eng, p, max_new=2):
        sched = Scheduler(max_queue=4)
        r = Request(np.asarray(p, np.int32), sys_mem,
                    max_new_tokens=max_new, eos_id=1)
        sched.submit(r)
        eng.serve_until_idle(sched, max_iterations=500)
        res = r.result(timeout=60)
        assert res.ok
        return res

    def warm(eng):
        # compile every program the timed phases will touch — join
        # bucket 128, attach (whole hit), cow (mid-page fork), and the
        # pattach pair for each fork depth — then drop the entries so
        # the batch phase rebuilds the trie from cold
        for p in ([np.concatenate([base, [5, 9, 2]]).astype("i4")] * 2
                  + [np.concatenate([base[:f], [3, 7, 12]]).astype("i4")
                     for f in forks]):
            serve_one(eng, p)
        eng.flush_prefix_cache()
        # warmup consulted the cache too — reset() zeroes every counter
        # (prefix hits included) so the snapshot reflects the timed
        # phases only, while keeping the engine's memory-ledger wiring
        # (TTFT is taken from per-request results, not metrics)
        eng.metrics.reset()

    def drive(eng):
        sched = Scheduler(max_queue=n_requests + 8)
        reqs = []
        t0 = time.perf_counter()
        for p, n_new in work:
            reqs.append(sched.submit(Request(
                p.copy(), sys_mem, max_new_tokens=n_new, eos_id=1)))
        eng.serve_until_idle(sched, max_iterations=20000)
        wall = time.perf_counter() - t0
        res = [r.result() for r in reqs]
        assert all(r.ok for r in res), \
            [r.finish_reason for r in res if not r.ok]
        ttft = np.asarray([r.ttft_s for r in res])
        toks = sum(len(r.tokens) for r in res)
        return res, ttft, toks, wall

    # ---- B side: same pool, whole-prompt reuse only (the flat
    # PrefixCache semantics PR 16 replaced) — forks re-prefill cold
    whole = mk_engine()
    whole._partial_ok = False
    warm(whole)
    w_res, w_ttft, w_toks, w_wall = drive(whole)

    # ---- A side: radix partial reuse, retrace sentinel armed over
    # the timed phases (warmup compiled every bucket pair)
    radix = mk_engine()
    warm(radix)
    with _maybe_trace("serving_radix") as trace_art:
        with retrace_sentinel(radix):
            r_res, r_ttft, r_toks, r_wall = drive(radix)

    # partial-hit generation bit-matches the whole-prompt side, whose
    # forked prompts all ran cold full prefills
    for a, b in zip(w_res, r_res):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    m = radix.metrics
    assert m.prefix_partial_hits >= 3, m.prefix_partial_hits
    snap = m.snapshot()["prefix"]
    assert snap["hit_token_ratio"] >= 0.5, snap
    # the default round_down policy serves mid-page forks from the
    # page boundary: no COW dispatches at all in the batch phase
    assert snap["cow_copies"] == 0, snap

    # ---- phase 2: paired sequential TTFT probes per fork depth.
    # max_new_tokens=1 makes TTFT the join cost itself (no queue
    # wait); fresh tails per rep keep every radix consult a PARTIAL
    # hit; order alternates per rep to cancel drift
    prs = np.random.RandomState(1)
    depth_win = {}
    with retrace_sentinel(radix):
        for f in forks:
            pairs = []
            for rep in range(probe_reps):
                t = prs.randint(2, vocab, (4,))
                p = np.concatenate([base[:f], t]).astype("i4")
                sides = [(whole, "w"), (radix, "r")]
                if rep % 2:
                    sides.reverse()
                got = {}
                for eng, tag in sides:
                    got[tag] = serve_one(eng, p, max_new=1).ttft_s
                pairs.append((got["w"], got["r"]))
            med_w = float(np.median([a for a, _ in pairs]))
            med_r = float(np.median([b for _, b in pairs]))
            depth_win[f] = {
                "whole_ttft_ms": round(med_w * 1e3, 2),
                "radix_ttft_ms": round(med_r * 1e3, 2),
                "win": round(med_w / max(med_r, 1e-9), 3)}
    # the TTFT win, in-bench: at least one page-aligned shared-
    # preamble depth must beat the whole-prompt-only side (the
    # ISSUE-16 acceptance bar). The headline is the best such depth —
    # per-depth medians ride along so the artifact shows the whole
    # curve, including the mid-page COW depth where the extra copy
    # dispatch can eat the win on this dispatch-bound box
    aligned = [f for f in forks if f % page_size == 0]
    best = max(aligned, key=lambda f: depth_win[f]["win"])
    assert depth_win[best]["win"] > 1.0, depth_win
    # round_down killed the mid-page regression row: the 40-token fork
    # seeds from the 32-token boundary with no COW dispatch, so it
    # must at least hold par with the whole-prompt side (the PR-16
    # committed row LOST ~0.7x here under mid_page="cow")
    for f in forks:
        if f % page_size:
            assert depth_win[f]["win"] > 0.9, depth_win

    # leak-free after the drain on both pools
    for eng in (whole, radix):
        eng.flush_prefix_cache()
        eng._alloc.check()
        assert eng._alloc.pages_free == eng.num_pages

    def pct(a, q):
        return round(float(np.percentile(a, q)) * 1e3, 1)

    return {"metric": "serving_radix",
            "value": depth_win[best]["win"],
            "unit": f"x lower TTFT at the best shared-preamble depth "
                    f"({best} tokens matched) vs whole-prompt-only "
                    f"reuse, paired sequential probes",
            "bitmatch_whole_prompt_cold": True,
            "leak_free_asserted": True,
            "retrace_sentinel": "armed over batch drive + probes",
            "ttft_by_depth": {str(k): v for k, v in depth_win.items()},
            **({} if trace_art[0] is None
               else {"trace_artifact": trace_art[0]}),
            "radix": {"ttft_p50_ms": pct(r_ttft, 50),
                      "ttft_p99_ms": pct(r_ttft, 99),
                      "tok_per_s": round(r_toks / r_wall, 1),
                      "hit_token_ratio": snap["hit_token_ratio"],
                      "whole_hits": snap["whole_hits"],
                      "partial_hits": snap["partial_hits"],
                      "misses": snap["misses"],
                      "cow_copies": snap["cow_copies"],
                      "rounded_down":
                          radix._prefix.stats()["rounded_down"],
                      "full_prefills": radix.prefill_count,
                      "wall_s": round(r_wall, 2)},
            "whole_prompt": {"ttft_p50_ms": pct(w_ttft, 50),
                             "ttft_p99_ms": pct(w_ttft, 99),
                             "tok_per_s": round(w_toks / w_wall, 1),
                             "full_prefills": whole.prefill_count,
                             "wall_s": round(w_wall, 2)},
            "config": {"n_requests": n_requests, "pre_len": pre_len,
                       "fork_depths": forks, "probe_reps": probe_reps,
                       "page_size": page_size, "num_slots": num_slots,
                       "num_pages": num_pages, "max_len": max_len,
                       "prefix_capacity": 8,
                       "max_new_tokens": "4..12 ragged (batch), "
                                         "1 (probes)"}}


def _serving_slo(n_batch=8, n_inter=10, d_model=64, nhead=2, ffn=128,
                 n_layers=2, vocab=64, mem_len=4, max_len=160,
                 page_size=8, num_slots=4, num_pages=224,
                 batch_len=64, batch_new=48, inter_new=6,
                 prefill_chunk=8, gap_reps=3):
    """Traffic shaping vs FIFO on the SAME paged pool at EQUAL offered
    load, three phases. Phase 1 (TTFT under mixed traffic): a bimodal
    open-loop drive — 8 long batch prompts (64 tokens) land at t=0,
    10 short interactive requests arrive Poisson-spaced through the
    busy window (arrival times calibrated to the measured FIFO wall so
    the pool is congested on both sides). Both twins run IDENTICAL
    `prefill_chunk=8` engines — the only variable is the scheduler:
    the FIFO twin admits in arrival order, the shaped side runs
    `ShapingScheduler` (interactive rank 0, batch preemptible), so
    interactive work jumps the queue and preempts batch slots to the
    prefix cache. Asserted: every request's tokens bit-match across
    the two sides (preempt/resume and chunking are invisible in
    output), interactive p99 TTFT wins by >= 1.5x, the shaped wall
    stays within 1.6x of FIFO (scheduling overhead — preemption
    replay plus WFQ bookkeeping — must not eat the equal offered
    load), resumes == preemptions >= 1 with prefill_count <= requests
    (a resume rides the trie attach, never a re-prefill), leak-free
    pools, retrace sentinel armed. Phase 2 (fairness): one hog tenant
    floods 10 requests ahead of a light tenant's 4 on a 2-slot pool;
    at a half-drain token horizon the Jain index over per-tenant
    delivered tokens must IMPROVE under WFQ vs FIFO (arrival order
    starves the light tenant; equal-weight WFQ alternates). Phase 3
    (step-gap bound): co-resident decoders see one long prompt join
    mid-stream — chunked prefill must keep decode-step inter-arrival
    p99 within 6x of a no-join baseline (median of 3 reps; the
    whole-prompt join's gap rides along unasserted for the curve)."""
    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving import (Request, Scheduler, ServingEngine,
                                    ShapingScheduler, retrace_sentinel)

    def mk_stack(seed=11):
        import paddle_tpu as paddle

        paddle.seed(seed)
        np.random.seed(seed)
        layer = TransformerDecoderLayer(d_model, nhead, ffn,
                                        dropout=0.0)
        dec = TransformerDecoder(layer, n_layers)
        dec.eval()
        return dec, nn.Embedding(vocab, d_model), nn.Linear(d_model,
                                                            vocab)

    def mk_engine(chunk, slots=num_slots):
        dec, embed, proj = mk_stack()
        return ServingEngine(dec, embed, proj, num_slots=slots,
                             max_len=max_len, paged=True,
                             page_size=page_size, num_pages=num_pages,
                             prefix_capacity=32, prefill_chunk=chunk)

    rs = np.random.RandomState(3)

    def mk_prompt(P):
        p = rs.randint(2, vocab, (P,)).astype(np.int32)
        p[0] = 0
        mem = np.random.RandomState(
            int(p.sum()) * 131 + P).randn(mem_len,
                                          d_model).astype("f4")
        return p, mem

    batch_specs = [mk_prompt(batch_len) + (batch_new,)
                   for _ in range(n_batch)]
    inter_specs = [mk_prompt(int(rs.randint(2, 8))) + (inter_new,)
                   for _ in range(n_inter)]

    def mk_reqs(slo=False):
        b = [Request(p.copy(), m, max_new_tokens=n, eos_id=1,
                     **({"slo": "batch"} if slo else {}))
             for p, m, n in batch_specs]
        i = [Request(p.copy(), m, max_new_tokens=n, eos_id=1,
                     **({"slo": "interactive"} if slo else {}))
             for p, m, n in inter_specs]
        return b, i

    resume_len = mk_prompt(batch_len + 8)   # a preempted batch slot's
    # prompt+generated length lands past batch_len: serving this pair
    # compiles the attach/chunk buckets a mid-drive resume rides

    def warm(eng):
        """Compile every program the timed drive touches (join bucket
        8, the pcjoin chunk family or the whole-prompt bucket, decode,
        and the whole-hit attach a resume rides), then reset counters
        and drop the trie so the timed phase starts cold. Returns the
        busy wall — only meaningful on a SECOND call, once every
        program is compiled (the calibration window)."""
        sched = Scheduler(max_queue=64)
        b, i = mk_reqs()
        reqs = b + i
        for p, m in (batch_specs[0][:2], resume_len[:2],
                     resume_len[:2]):     # repeats: whole-hit attach
            reqs.append(Request(p.copy(), m, max_new_tokens=2,
                                eos_id=1))
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        eng.serve_until_idle(sched, max_iterations=5000)
        wall = time.perf_counter() - t0
        assert all(r.result(timeout=5).ok for r in reqs)
        eng.flush_prefix_cache()
        eng.metrics.reset()
        return wall

    def timed_drive(eng, sched, schedule):
        """Open-loop: submit each request at its wall-clock arrival
        time while the engine iterates; returns the busy wall."""
        idx = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while idx < len(schedule) and schedule[idx][0] <= now:
                sched.submit(schedule[idx][1])
                idx += 1
            if sched.depth() == 0 and eng.occupancy() == 0:
                if idx >= len(schedule):
                    break
                time.sleep(max(0.0, min(
                    0.002,
                    schedule[idx][0] - (time.perf_counter() - t0))))
                continue
            eng.run_iteration(sched)
        return time.perf_counter() - t0

    # ---- phase 1: bimodal mixed traffic, shaped vs FIFO twin ----
    # the twins run IDENTICAL chunked engines: per-chunk dispatch on a
    # 1-core CPU costs as much as a decode step, so an unchunked FIFO
    # baseline would fold that fixed cost into the scheduler
    # comparison — phase 3 quantifies chunking itself against a
    # no-join baseline instead
    fifo = mk_engine(prefill_chunk)
    shaped = mk_engine(prefill_chunk)
    warm(shaped)
    warm(fifo)              # first pass compiles
    cal_wall = warm(fifo)   # the congestion window both sides share
    ars = np.random.RandomState(7)
    gaps = np.cumsum(ars.exponential(1.0, n_inter))
    arrive = 0.05 * cal_wall + 0.55 * cal_wall * gaps / gaps[-1]

    def schedule_for(slo):
        b, i = mk_reqs(slo=slo)
        sched = [(0.0, r) for r in b] + list(zip(arrive, i))
        return b, i, sorted(sched, key=lambda e: e[0])

    out = {}
    with _maybe_trace("serving_slo") as trace_art:
        fb, fi, fsched = schedule_for(slo=False)
        f_wall = timed_drive(fifo, Scheduler(max_queue=64), fsched)
        sb, si, ssched = schedule_for(slo=True)
        pc0 = shaped.prefill_count   # engine-lifetime counter: the
        # warm passes' prefills stay in it, only the delta is ours
        with retrace_sentinel(shaped):
            s_wall = timed_drive(
                shaped, ShapingScheduler(max_queue=64,
                                         max_preemptions=1,
                                         metrics=shaped.metrics),
                ssched)
    f_res = [r.result(timeout=5) for r in fb + fi]
    s_res = [r.result(timeout=5) for r in sb + si]
    assert all(r.ok for r in f_res) and all(r.ok for r in s_res)
    # preempt/resume + chunking are invisible in output: every request
    # bit-matches its FIFO twin
    for a, b in zip(f_res, s_res):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    m = shaped.metrics
    assert m.preemptions >= 1, m.preemptions
    assert m.resumes == m.preemptions, (m.resumes, m.preemptions)
    assert m.chunked_prefills >= n_batch, m.chunked_prefills
    # a resume rides the whole-hit trie attach: joins = requests +
    # resumes, yet real prefill programs never exceed the request
    # count (re-prefilling a preempted slot would push it past)
    n_requests = n_batch + n_inter
    prefills = shaped.prefill_count - pc0
    assert prefills <= n_requests, (prefills, n_requests)
    assert m.joins >= n_requests + m.resumes, (m.joins, m.resumes)
    fi_ttft = np.asarray([r.ttft_s for r in f_res[n_batch:]])
    si_ttft = np.asarray([r.ttft_s for r in s_res[n_batch:]])
    f_p99 = float(np.percentile(fi_ttft, 99))
    s_p99 = float(np.percentile(si_ttft, 99))
    ttft_win = f_p99 / max(s_p99, 1e-9)
    assert ttft_win >= 1.5, (f_p99, s_p99)
    # equal offered load on identical engines: the scheduler's own
    # overhead (preemption replay + WFQ bookkeeping) must not blow up
    # the busy wall
    assert s_wall <= f_wall * 1.6, (s_wall, f_wall)
    for eng in (fifo, shaped):
        eng.flush_prefix_cache()
        eng._alloc.check()
        assert eng._alloc.pages_free == eng.num_pages

    # ---- phase 2: WFQ fairness at a half-drain horizon ----
    def jain(xs):
        xs = np.asarray(xs, np.float64)
        return float(xs.sum() ** 2
                     / (len(xs) * (xs ** 2).sum() + 1e-12))

    def fairness_side(shaped_side):
        from paddle_tpu.serving import AdapterPool

        import paddle_tpu as paddle

        paddle.seed(11)
        np.random.seed(11)
        layer = TransformerDecoderLayer(d_model, nhead, ffn,
                                        dropout=0.0)
        dec = TransformerDecoder(layer, n_layers)
        dec.eval()
        embed = nn.Embedding(vocab, d_model)
        proj = nn.Linear(d_model, vocab)
        apool = AdapterPool(dec, capacity=3, rank=4)
        apool.register_random("hog", seed=201, scale=0.05)
        apool.register_random("light", seed=202, scale=0.05)
        eng = ServingEngine(dec, embed, proj, num_slots=2,
                            max_len=64, adapters=apool)
        frs = np.random.RandomState(9)
        reqs = []
        for tenant, n in (("hog", 10), ("light", 4)):
            for _ in range(n):
                P = int(frs.randint(3, 7))
                p = frs.randint(2, vocab, (P,)).astype(np.int32)
                p[0] = 0
                mem = np.random.RandomState(
                    int(p.sum()) * 131 + P).randn(
                        mem_len, d_model).astype("f4")
                reqs.append((tenant, Request(
                    p, mem, max_new_tokens=16, eos_id=1,
                    adapter=tenant)))
        sched = (ShapingScheduler(max_queue=32) if shaped_side
                 else Scheduler(max_queue=32))
        for _, r in reqs:      # the hog's flood submits FIRST
            sched.submit(r)
        total = sum(r.max_new_tokens for _, r in reqs)

        def delivered():
            return sum(len(r.tokens) for _, r in reqs)

        it = 0
        while delivered() < total // 2 and it < 2000:
            eng.run_iteration(sched)
            it += 1
        by_tenant = {"hog": 0, "light": 0}
        for tenant, r in reqs:
            by_tenant[tenant] += len(r.tokens)
        j = jain([by_tenant["hog"], by_tenant["light"]])
        eng.serve_until_idle(sched, max_iterations=5000)
        assert all(r.result(timeout=5).ok for _, r in reqs)
        return j, by_tenant

    j_fifo, t_fifo = fairness_side(shaped_side=False)
    j_wfq, t_wfq = fairness_side(shaped_side=True)
    assert j_wfq > j_fifo, (j_wfq, j_fifo)

    # ---- phase 3: chunked prefill bounds the decode-step gap ----
    def gap_run(chunk, with_long):
        eng = mk_engine(chunk)
        warm(eng)
        sched = Scheduler(max_queue=16)
        decs = [Request(p.copy(), m, max_new_tokens=40, eos_id=1)
                for p, m, _ in inter_specs[:3]]
        for r in decs:
            sched.submit(r)
        for _ in range(3):
            eng.run_iteration(sched)
        reqs = list(decs)
        if with_long:
            p, m, _ = batch_specs[0]
            reqs.append(Request(p.copy(), m, max_new_tokens=1,
                                eos_id=1))
            sched.submit(reqs[-1])
        eng.serve_until_idle(sched, max_iterations=2000)
        assert all(r.result(timeout=5).ok for r in reqs)
        # the gauge is recorded on every engine but only the sharded
        # snapshot renders a "sharding" section — read the reservoir
        return eng.metrics.step_gap_s.summary(scale=1e3)["p99"]

    base_p99 = float(np.median(
        [gap_run(prefill_chunk, False) for _ in range(gap_reps)]))
    chunk_p99 = float(np.median(
        [gap_run(prefill_chunk, True) for _ in range(gap_reps)]))
    whole_p99 = float(np.median(
        [gap_run(None, True) for _ in range(gap_reps)]))
    assert chunk_p99 <= base_p99 * 6.0, (chunk_p99, base_p99)

    def pct(a, q):
        return round(float(np.percentile(a, q)) * 1e3, 1)

    snap = m.snapshot()["slo"]
    out.update({
        "metric": "serving_slo",
        "value": round(ttft_win, 2),
        "unit": "x lower interactive p99 TTFT vs the FIFO twin at "
                "equal offered load (bimodal open-loop drive)",
        "bitmatch_fifo_twin": True,
        "leak_free_asserted": True,
        "retrace_sentinel": "armed over the shaped timed drive",
        "interactive_ttft": {
            "fifo_p50_ms": pct(fi_ttft, 50),
            "fifo_p99_ms": pct(fi_ttft, 99),
            "shaped_p50_ms": pct(si_ttft, 50),
            "shaped_p99_ms": pct(si_ttft, 99)},
        "walls": {"fifo_s": round(f_wall, 2),
                  "shaped_s": round(s_wall, 2)},
        "shaping": {"preemptions": snap["preemptions"],
                    "resumes": snap["resumes"],
                    "replay_tokens": snap["replay_tokens"],
                    "chunked_prefills": snap["chunked_prefills"],
                    "chunks": snap["chunks"],
                    "full_prefills": prefills,
                    "ttft_attainment": snap["ttft_attainment"]},
        "fairness": {"jain_fifo": round(j_fifo, 3),
                     "jain_wfq": round(j_wfq, 3),
                     "tokens_fifo": t_fifo, "tokens_wfq": t_wfq},
        "step_gap_p99_ms": {
            "no_join_baseline": round(base_p99, 2),
            "chunked_join": round(chunk_p99, 2),
            "whole_prompt_join": round(whole_p99, 2),
            "chunked_vs_baseline": round(
                chunk_p99 / max(base_p99, 1e-9), 2)},
        **({} if trace_art[0] is None
           else {"trace_artifact": trace_art[0]}),
        "config": {"n_batch": n_batch, "n_inter": n_inter,
                   "batch_len": batch_len, "batch_new": batch_new,
                   "inter_new": inter_new,
                   "prefill_chunk": prefill_chunk,
                   "page_size": page_size, "num_slots": num_slots,
                   "num_pages": num_pages, "gap_reps": gap_reps}})
    return out


def _serving_multitenant(n_tenants=4, d_model=64, nhead=2, ffn=128,
                         n_layers=2, vocab=64, mem_len=4, rank=8,
                         reqs_per_tenant=4, max_new=24,
                         shared_slots=16, per_tenant_slots=2, pairs=3):
    """Multi-tenant serving A/B at EQUAL HBM budget: one shared pool
    serving N tenants' mixed traffic through batched LoRA adapters
    over an int8 base, vs the naive deployment — one fp32 engine PER
    TENANT (adapter deltas merged into its weights) serving its own
    requests serially. The budget is the naive side's ledger total
    (N weight copies + N small pools); the shared side must FIT UNDER
    it (asserted via memory_ledger) while batching every tenant into
    one decode dispatch — the aggregate tokens/s ratio is the
    headline, asserted >= 2x. The int8 base must also come in >= 1.9x
    under the fp32 weight ledger (asserted exactly from the ledgers).
    Correctness is asserted in-bench: every shared-pool request's
    tokens must equal its tenant's merged-weight engine output
    token-for-token. PAIRED per-pair ratio, alternating order,
    median-of-pairs (the repo's 1-core noise discipline)."""
    import jax  # noqa: F401  (engine imports lazily)

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.serving import AdapterPool, ServingEngine
    from paddle_tpu.serving.scheduler import Request, Scheduler

    def mk_stack(seed):
        # reset BOTH rngs: initializers draw from paddle's key
        # stream, so same-seed reconstruction (the A/B's identical
        # base weights) needs it reset alongside numpy
        import paddle_tpu as paddle

        paddle.seed(seed)
        np.random.seed(seed)
        layer = TransformerDecoderLayer(d_model, nhead, ffn,
                                        dropout=0.0)
        dec = TransformerDecoder(layer, n_layers)
        dec.eval()
        return dec, nn.Embedding(vocab, d_model), nn.Linear(d_model,
                                                            vocab)

    tenants = [f"tenant{i}" for i in range(n_tenants)]

    # ---- B side: one fp32 merged-weight engine per tenant ----
    # every tenant engine clones the SAME base stack construction
    # (same seed -> identical weights) and merges its adapter in
    naive = {}
    pool_ref = None
    for ti, name in enumerate(tenants):
        dec, embed, proj = mk_stack(11)
        pool = AdapterPool(dec, capacity=n_tenants + 1, rank=rank)
        for tj, nm in enumerate(tenants):
            pool.register_random(nm, seed=100 + tj, scale=0.05)
        if pool_ref is None:
            pool_ref = pool
        for i, w in pool.merged_weights(name):
            pool.targets[i].weight._data = w
        naive[name] = ServingEngine(dec, embed, proj,
                                    num_slots=per_tenant_slots,
                                    max_len=64)
    # ---- A side: ONE shared pool, int8 base + adapter banks ----
    dec, embed, proj = mk_stack(11)
    apool = AdapterPool(dec, capacity=n_tenants + 1, rank=rank)
    for tj, nm in enumerate(tenants):
        apool.register_random(nm, seed=100 + tj, scale=0.05)
    shared = ServingEngine(dec, embed, proj, num_slots=shared_slots,
                           max_len=64, adapters=apool, quantize="int8")
    # the CORRECTNESS twin: the same shared pool at fp32 — the
    # factored adapter path must be token-identical to the merged
    # weights; the int8 perf side is only tolerance-bounded (weight
    # rounding can flip an argmax on a tiny bench model)
    dec32, embed32, proj32 = mk_stack(11)
    apool32 = AdapterPool(dec32, capacity=n_tenants + 1, rank=rank)
    for tj, nm in enumerate(tenants):
        apool32.register_random(nm, seed=100 + tj, scale=0.05)
    shared32 = ServingEngine(dec32, embed32, proj32,
                             num_slots=shared_slots, max_len=64,
                             adapters=apool32)

    rs = np.random.RandomState(5)
    prompts = []
    for name in tenants:
        for _ in range(reqs_per_tenant):
            P = int(rs.randint(2, 7))
            p = rs.randint(2, vocab, (P,)).astype(np.int32)
            p[0] = 0
            mem = np.random.RandomState(
                int(p.sum()) * 131 + P).randn(mem_len,
                                              d_model).astype("f4")
            prompts.append((name, p, mem))

    def serve_shared(eng=None):
        eng = eng if eng is not None else shared
        sched = Scheduler(max_queue=64)
        reqs = []
        for name, p, mem in prompts:
            r = Request(p.copy(), mem, max_new_tokens=max_new,
                        eos_id=1, adapter=name)
            reqs.append((name, r))
            sched.submit(r)
        t0 = time.perf_counter()
        eng.serve_until_idle(sched)
        dt = time.perf_counter() - t0
        toks = [(name, list(r.result(timeout=5).tokens))
                for name, r in reqs]
        return sum(len(t) for _, t in toks) / dt, toks

    def serve_naive():
        total = 0
        t0 = time.perf_counter()
        toks = []
        for name in tenants:
            sched = Scheduler(max_queue=64)
            reqs = []
            for nm, p, mem in prompts:
                if nm != name:
                    continue
                r = Request(p.copy(), mem, max_new_tokens=max_new,
                            eos_id=1)
                reqs.append(r)
                sched.submit(r)
            naive[name].serve_until_idle(sched)
            for r in reqs:
                t = list(r.result(timeout=5).tokens)
                toks.append((name, t))
                total += len(t)
        dt = time.perf_counter() - t0
        return total / dt, toks

    out = {}
    with _maybe_trace("serving_multitenant") as trace_art:
        serve_shared()            # compile both sides
        serve_naive()
        ratios, a_s, b_s = [], [], []
        toks_a = toks_b = None
        for i in range(pairs):
            order = (serve_naive, serve_shared) if i % 2 == 0 \
                else (serve_shared, serve_naive)
            x_tps, x_toks = order[0]()
            y_tps, y_toks = order[1]()
            if order[0] is serve_naive:
                bt, at = x_tps, y_tps
                toks_b, toks_a = x_toks, y_toks
            else:
                bt, at = y_tps, x_tps
                toks_b, toks_a = y_toks, x_toks
            ratios.append(at / bt)
            a_s.append(at)
            b_s.append(bt)
    # correctness: the fp32 shared pool's factored adapter decode ==
    # merged-weight solo engines, token for token, per request — the
    # acceptance bit-match (sorted into the same multiset order)
    _, toks_32 = serve_shared(shared32)
    if sorted(map(repr, toks_32)) != sorted(map(repr, toks_b)):
        raise AssertionError(
            "fp32 shared multi-tenant pool diverged from the "
            "per-tenant merged-weight engines")
    # int8 perf side: tolerance-bounded, not bit-exact — record the
    # token agreement vs the fp32 twin and require it not collapse
    agree = tot = 0
    for (na, ta), (n3, t3) in zip(sorted(toks_a), sorted(toks_32)):
        for x, y in zip(ta, t3):
            tot += 1
            agree += int(x == y)
    int8_agreement = agree / max(1, tot)
    if int8_agreement < 0.8:
        raise AssertionError(
            f"int8 shared pool token agreement collapsed vs fp32: "
            f"{int8_agreement:.3f}")
    # equal-HBM budget: the shared side fits under the naive total
    shared_mem = shared.metrics.snapshot()["memory"]
    naive_mems = [e.metrics.snapshot()["memory"]
                  for e in naive.values()]
    budget = sum(m["total_bytes"] for m in naive_mems)
    if shared_mem["total_bytes"] > budget:
        raise AssertionError(
            f"shared pool ({shared_mem['total_bytes']}b) exceeds the "
            f"naive deployment's HBM budget ({budget}b)")
    # int8 base >= 1.9x under ONE fp32 copy (weights only, exact)
    w_ratio = naive_mems[0]["weights_bytes"] / \
        shared_mem["weights_bytes"]
    if w_ratio < 1.9:
        raise AssertionError(
            f"int8 weight ledger only {w_ratio:.2f}x under fp32 "
            f"(>= 1.9x required)")
    ratios.sort()
    med = ratios[len(ratios) // 2]
    if med < 2.0:
        raise AssertionError(
            f"shared multi-tenant pool below the 2x aggregate "
            f"tokens/s floor vs serial per-tenant pools: {med:.2f}x "
            f"(shared {sorted(a_s)}, naive {sorted(b_s)})")
    snap = shared.metrics.snapshot()
    out = {
        "metric": "serving_multitenant",
        "value": round(med, 2),
        "unit": "x aggregate tokens/s vs serial per-tenant fp32 "
                "pools at equal HBM budget",
        **({} if trace_art[0] is None
           else {"trace_artifact": trace_art[0]}),
        "shared_tok_per_s": round(sorted(a_s)[pairs // 2], 1),
        "naive_tok_per_s": round(sorted(b_s)[pairs // 2], 1),
        "weights_int8_bytes": shared_mem["weights_bytes"],
        "weights_f32_bytes": naive_mems[0]["weights_bytes"],
        "int8_weight_shrink": round(w_ratio, 2),
        "adapter_bytes": shared_mem["adapter_bytes"],
        "shared_total_bytes": shared_mem["total_bytes"],
        "naive_total_bytes": budget,
        "adapter_hit_rate": snap["tenancy"]["adapter_hit_rate"],
        "fairness": snap["tenancy"]["fairness"],
        "bit_match_asserted": "fp32 shared pool == merged-weight "
                              "per-tenant engines",
        "int8_token_agreement": round(int8_agreement, 3),
        "spread": _spread(ratios, kind="pairs"),
        "config": {"n_tenants": n_tenants, "rank": rank,
                   "shared_slots": shared_slots,
                   "per_tenant_slots": per_tenant_slots,
                   "reqs_per_tenant": reqs_per_tenant,
                   "max_new": max_new, "d_model": d_model,
                   "vocab": vocab,
                   "workload": "mixed-tenant random prompts, one "
                               "shared int8+LoRA pool vs N resident "
                               "fp32 merged-weight pools served "
                               "serially"}}
    return out


def _serving_sharded(n_requests=24, d_model=64, nhead=2, ffn=128,
                     n_layers=2, vocab=128, mem_len=4, max_new=10,
                     prompt_max=8, dense_slots=4, long_prompt=40,
                     resident_new=48):
    """Mesh-sharded serving A/B on the 8-virtual-device CPU mesh.

    Part 1 — pool scaling at EQUAL per-device cache memory: the
    single-chip engine gets `dense_slots` rows on one CPU device; the
    sharded engine (dp=2 x fsdp=2 x tp=2) gets `2 * dense_slots` rows
    sharded over dp — the same rows-per-device budget, with weights
    laid out fsdp x tp in the bit-exact "gathered" layout. The bench
    ASSERTS every request's tokens bit-match between the two pools.
    CPU caveat: one host core executes all 8 virtual devices, so
    tokens/s measures structure and overhead, not the memory-capacity
    scaling a real pod sees (the pool and the weights it can hold DO
    scale with the mesh; wall clock here cannot).

    Part 2 — prefill/decode disaggregation under concurrent long-prompt
    joins: 4 resident requests decode while a long-prompt (bucket-64)
    request joins EVERY iteration. Inline prefill blocks each iteration
    on the full prompt prefill; the disaggregated engine dispatches it
    to the prefill dp slice and splices asynchronously. The metric is
    the decode-step inter-arrival p50 (`step_gap_ms`) the residents
    see between their tokens; the bench asserts the disaggregated
    path's p50 is LOWER."""
    import os

    # read when the CPU backend starts, so it must be set before the
    # first device query; it changes nothing on an accelerator backend
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    # the DEFAULT backend's devices: on a chip machine this never moves
    # to the CPU behind the caller's back
    devs = jax.devices()
    if len(devs) < 8:
        return {"metric": "serving_sharded",
                "status": f"skipped: needs 8 devices, the "
                          f"{jax.default_backend()} backend has "
                          f"{len(devs)} (on the CPU backend "
                          f"XLA_FLAGS=--xla_force_host_platform_"
                          f"device_count=8 gives them)"}

    from paddle_tpu import nn
    from paddle_tpu.nn.layer.transformer import (TransformerDecoder,
                                                 TransformerDecoderLayer)
    from paddle_tpu.parallel import init_mesh
    from paddle_tpu.serving import (Request, Scheduler, ServingEngine,
                                    ShardedServingEngine)

    layer = TransformerDecoderLayer(d_model, nhead, ffn, dropout=0.0)
    dec = TransformerDecoder(layer, n_layers)
    dec.eval()
    embed = nn.Embedding(vocab, d_model)
    proj = nn.Linear(d_model, vocab)
    rs = np.random.RandomState(0)
    mesh = init_mesh(dp=2, fsdp=2, tp=2, devices=devs[:8])

    max_len = (1 << (prompt_max - 1).bit_length()) + max_new
    work = []
    for _ in range(n_requests):
        P = int(rs.randint(1, prompt_max + 1))
        p = rs.randint(2, vocab, (P,)).astype("i4")
        p[0] = 0
        work.append((p, rs.randn(mem_len, d_model).astype("f4")))

    def drive(eng):
        sched = Scheduler(max_queue=n_requests + 8)
        reqs = []
        t0 = time.perf_counter()
        for p, m in work:
            reqs.append(sched.submit(Request(
                p.copy(), m, max_new_tokens=max_new, eos_id=1)))
        eng.serve_until_idle(sched, max_iterations=20000)
        wall = time.perf_counter() - t0
        res = [r.result() for r in reqs]
        assert all(r.ok for r in res)
        ttft = np.asarray([r.ttft_s for r in res])
        toks = sum(len(r.tokens) for r in res)
        return res, ttft, toks, wall

    with jax.default_device(devs[0]):   # pin the 1-chip side to ONE
        #                                 device for a fair A/B
        dense = ServingEngine(dec, embed, proj, num_slots=dense_slots,
                              max_len=max_len, max_joins_per_iter=4)
        d_res, d_ttft, d_toks, d_wall = drive(dense)

    shard = ShardedServingEngine(dec, embed, proj, mesh=mesh,
                                 num_slots=2 * dense_slots,
                                 max_len=max_len, max_joins_per_iter=4)
    with _maybe_trace("serving_sharded") as trace_art:
        s_res, s_ttft, s_toks, s_wall = drive(shard)

    # the acceptance bit-match: fp32 gathered layout, per request
    for a, b in zip(d_res, s_res):
        np.testing.assert_array_equal(a.tokens, b.tokens)

    # ---- part 2: disaggregated vs inline prefill ----
    LONG_MAXLEN = (1 << (long_prompt - 1).bit_length()) + 16
    lp = rs.randint(2, vocab, (long_prompt,)).astype("i4")
    lp[0] = 0
    lmem = rs.randn(mem_len, d_model).astype("f4")
    residents = []
    for _ in range(4):
        p = rs.randint(2, vocab, (2,)).astype("i4")
        p[0] = 0
        residents.append((p, rs.randn(mem_len, d_model).astype("f4")))

    def measure(policy):
        eng = ShardedServingEngine(dec, embed, proj, mesh=mesh,
                                   num_slots=6, max_len=LONG_MAXLEN,
                                   prefill=policy,
                                   max_joins_per_iter=1)
        sched = Scheduler(max_queue=512)
        warm = []
        for p, m in [(lp, lmem), residents[0]]:
            r = Request(p.copy(), m, max_new_tokens=1, eos_id=None)
            sched.submit(r)
            warm.append(r)
        eng.serve_until_idle(sched, max_iterations=200)
        res = [Request(p.copy(), m, max_new_tokens=resident_new,
                       eos_id=None) for p, m in residents]
        for r in res:
            sched.submit(r)
        for _ in range(6):              # join the residents
            eng.run_iteration(sched)
        n0 = len(eng.metrics.step_gap_s._buf)
        n_long = 0
        it = 0
        while any(r.state != "DONE" for r in res):
            sched.submit(Request(lp.copy(), lmem, max_new_tokens=2,
                                 eos_id=None))
            n_long += 1
            eng.run_iteration(sched)
            it += 1
            assert it < 1000
        gaps = np.asarray(eng.metrics.step_gap_s._buf[n0:]) * 1e3
        eng.abort_active("shutdown")
        sched.abort_queued("shutdown")
        sh = eng.metrics.snapshot()["sharding"]
        return gaps, n_long, sh

    inline_gaps, inline_longs, _ = measure("inline")
    dis_gaps, dis_longs, dis_sh = measure("disaggregated")
    inline_p50 = float(np.percentile(inline_gaps, 50))
    dis_p50 = float(np.percentile(dis_gaps, 50))
    # the acceptance: disaggregated prefill stops stealing decode-step
    # latency from co-resident requests
    assert dis_p50 < inline_p50, (dis_p50, inline_p50)

    def pct(a, q):
        return round(float(np.percentile(a, q)) * 1e3, 1)

    return {"metric": "serving_sharded",
            "value": round(inline_p50 / dis_p50, 2),
            "unit": "x lower decode-step p50 with disaggregated "
                    "prefill under concurrent long-prompt joins",
            "bitmatch_single_chip": True,
            **({} if trace_art[0] is None
               else {"trace_artifact": trace_art[0]}),
            "pool_scaling": {
                "dense_1dev": {"slots": dense_slots,
                               "tok_per_s": round(d_toks / d_wall, 1),
                               "ttft_p50_ms": pct(d_ttft, 50),
                               "wall_s": round(d_wall, 2)},
                "sharded_8dev": {"slots": 2 * dense_slots,
                                 "mesh": "dp2 x fsdp2 x tp2",
                                 "tok_per_s": round(s_toks / s_wall,
                                                    1),
                                 "ttft_p50_ms": pct(s_ttft, 50),
                                 "wall_s": round(s_wall, 2)},
                "note": "equal rows-per-device; CPU mesh measures "
                        "structure, not bandwidth"},
            "disaggregation": {
                "inline_step_gap_p50_ms": round(inline_p50, 2),
                "disagg_step_gap_p50_ms": round(dis_p50, 2),
                "inline_long_joins": inline_longs,
                "disagg_long_joins": dis_longs,
                "prefill_step_p50_ms":
                    dis_sh["prefill_step_ms"].get("p50"),
                "collective_time_share":
                    dis_sh["collective_time_share"]},
            "config": {"n_requests": n_requests, "d_model": d_model,
                       "layers": n_layers, "max_new_tokens": max_new,
                       "long_prompt_len": long_prompt,
                       "layout": "gathered (bit-exact)"}}


def _multichip_scaling(devices=None, sizes_mb=(4, 64), ar_iters=8,
                       dp_steps=6):
    """Config 4 harness: fleet collective allreduce bandwidth + DP weak
    scaling. Runs whenever >1 device is visible — real chips on a pod
    host, or the 8-virtual-device CPU mesh the test suite pins — so the
    moment multi-chip hardware appears, `python bench.py multichip`
    measures the BASELINE.md north star (fleet allreduce GB/s, >70%
    linear scaling) with no new code. On this 1-chip host the full bench
    records it as skipped; the CPU-mesh test keeps the path honest.

    busbw uses the standard ring-allreduce accounting: each device moves
    2*(N-1)/N of the buffer over the links per allreduce."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if n < 2:
        return {"metric": "fleet_allreduce_scaling",
                "status": "skipped: single real chip; harness validated "
                          "on the 8-device CPU mesh "
                          "(tests/test_parallel.py) and by "
                          "__graft_entry__.dryrun_multichip(8)"}
    from paddle_tpu.parallel.mesh import shard_map

    mesh = Mesh(np.array(devs), ("dp",))
    bands = {}
    for mb in sizes_mb:
        elems = (mb << 20) // 4
        per = -(-elems // n)

        @jax.jit
        @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"))
        def reduce_k(x):
            def body(c, _):
                # typed scale (weak python /n breaks the carry type) and
                # pvary (psum output is axis-invariant; the carry came
                # in dp-varying — scan requires matching varying axes)
                r = jax.lax.psum(c, "dp") * jnp.float32(1.0 / n)
                return jax.lax.pvary(r, "dp"), None
            c, _ = jax.lax.scan(body, x, None, length=ar_iters)
            return c

        x = jnp.ones((n * per,), jnp.float32)
        float(reduce_k(x).sum())          # compile + warm
        t0 = time.perf_counter()
        float(reduce_k(x).sum())          # readback bounds completion
        dt = (time.perf_counter() - t0) / ar_iters
        algbw = (elems * 4) / dt
        bands[f"{mb}MB"] = {
            "algbw_GBps": round(algbw / 1e9, 3),
            "busbw_GBps": round(algbw * 2 * (n - 1) / n / 1e9, 3)}

    # DP weak scaling: fixed per-device batch, same jitted step on a
    # 1-device mesh vs the full mesh
    import paddle_tpu.nn as pnn
    from paddle_tpu.optimizer import functional as fopt
    from paddle_tpu.parallel import SpmdTrainer, init_mesh

    def make_trainer(sub):
        m = init_mesh(dp=len(sub), devices=sub)
        net = pnn.Sequential(pnn.Linear(256, 512), pnn.ReLU(),
                             pnn.Linear(512, 10))

        ce = _softmax_ce

        tr = SpmdTrainer(net, ce, fopt.momentum(0.1, 0.9), mesh=m)
        B = 512 * len(sub)
        xs = np.random.RandomState(1).randn(B, 256).astype("f4")
        ys = np.random.RandomState(2).randint(0, 10, (B,)).astype("i8")
        dx, dy = tr.shard_batch(xs, ys)
        # warm the SAME step count: run_steps caches jitted loops per n,
        # so warming n=2 and timing n=dp_steps would time a compile
        float(tr.run_steps((dx,), dy, dp_steps))
        t0 = time.perf_counter()
        float(tr.run_steps((dx,), dy, dp_steps))
        return B * dp_steps / (time.perf_counter() - t0)

    tput1 = make_trainer(devs[:1])
    tputn = make_trainer(devs)
    eff = (tputn / n) / tput1
    return {"metric": "fleet_allreduce_scaling",
            "n_devices": n,
            "allreduce": bands,
            "dp_weak_scaling": {
                "tput_1dev_ex_per_s": round(tput1, 1),
                f"tput_{n}dev_ex_per_s": round(tputn, 1),
                "efficiency": round(eff, 3),
                "target": ">0.70 linear scaling (BASELINE.md)"}}


CONFIG_TIMEOUT_S = 1500

_DETAILS_PATH = None


def _details_path():
    """BENCH_DETAILS.json next to this script, independent of cwd (the
    per-config subprocesses run with cwd = script dir; the parent must
    read the same file)."""
    global _DETAILS_PATH
    if _DETAILS_PATH is None:
        import os

        _DETAILS_PATH = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_DETAILS.json")
    return _DETAILS_PATH


def _read_details():
    try:
        with open(_details_path()) as f:
            return json.load(f)
    except Exception:
        return {}


def main():
    global _TRACE
    argv = list(sys.argv[1:])
    _TRACE = "--trace" in argv
    argv = [a for a in argv if a != "--trace"]
    only = argv[0] if argv else None
    configs = [("mnist", _mnist_static), ("resnet50", _resnet50),
               ("ernie", _ernie), ("ctr_ps", _ctr_dnn_ps),
               ("long_context", _long_context_attention),
               ("ernie_long", _ernie_long),
               ("packed_varlen", _packed_varlen),
               ("fused_optimizer", _fused_optimizer),
               ("decode_throughput", _decode_throughput),
               ("cold_start", _cold_start),
               ("serving_throughput", _serving_throughput),
               ("serving_paged", _serving_paged),
               ("serving_paged_spec", _serving_paged_spec),
               ("serving_radix", _serving_radix),
               ("serving_slo", _serving_slo),
               ("serving_multitenant", _serving_multitenant),
               ("serving_sharded", _serving_sharded),
               ("multichip_scaling", _multichip_scaling)]
    results = {}
    headline = None
    if only is None:
        # full run: one subprocess per config with a hard timeout, so a
        # pathological backend compile can stall ONE config, never the
        # bench. This parent never touches jax: a chip belongs to one
        # process at a time, and each child needs it
        import os
        import subprocess

        for name, _ in configs:
            # clear any stale record first: a child that dies before
            # writing must surface as an error, not last run's number
            stale = _read_details()
            if name in stale:
                stale.pop(name)
                with open(_details_path(), "w") as f:
                    json.dump(stale, f, indent=1)
            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), name]
                    + (["--trace"] if _TRACE else []),
                    timeout=CONFIG_TIMEOUT_S,
                    stdout=subprocess.DEVNULL,
                    cwd=os.path.dirname(os.path.abspath(__file__)))
                if proc.returncode != 0:
                    results[name] = {
                        "metric": name,
                        "error": f"subprocess exited {proc.returncode}"}
            except subprocess.TimeoutExpired:
                results[name] = {
                    "metric": name,
                    "error": f"timeout after {CONFIG_TIMEOUT_S}s"}
        merged = _read_details()
        for name, _ in configs:  # subprocesses merged their own entries
            if name in merged and name not in results:
                results[name] = merged[name]
            results.setdefault(name, {"metric": name,
                                      "error": "config produced no record"})
        er = results.get("ernie") or {}
        headline = er if "value" in er else None
    for name, fn in configs:
        if only != name:
            continue
        from paddle_tpu.core import compile_cache

        compile_cache.enable()
        try:
            r = fn()
        except Exception as e:  # record it; the exit code reports it
            import traceback

            traceback.print_exc()
            r = {"metric": name, "error": f"{type(e).__name__}: {e}"}
        results[name] = r
        print(f"# {name}: {json.dumps(r)}", file=sys.stderr)
        if "value" in r:
            headline = r  # single-config runs headline themselves
    try:
        # MERGE into the record instead of clobbering other entries
        # (other configs' results, sweep records)
        merged = _read_details()
        merged.update(results)
        with open(_details_path(), "w") as f:
            json.dump(merged, f, indent=1)
    except Exception:
        pass
    if headline is None:
        # a config errored (or an unknown name was asked for): report the
        # failure honestly, never a fabricated 0.0 measurement
        headline = results.get("ernie") or {
            "metric": only or "ernie_base_finetune_seq_per_sec_per_chip",
            "error": "config did not produce a measurement"}
    print(json.dumps(headline))
    # a config that raised, timed out or left no record fails the run
    failed = sorted(n for n, r in results.items() if "error" in r)
    if failed or not results:
        print(f"# bench FAILED: {failed or only}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
