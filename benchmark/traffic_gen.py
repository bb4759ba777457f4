"""The one generator of traffic. A mix is a data file under
`benchmark/traffic/`; this file reads its parameters and nothing else
decides what is sent.

Steadiness rule (the contract's): a seed must not change the WORK, only
its order and its contents. So the sizes of a mix (prompt length and new
tokens of each request) are drawn once from the mix's own `sizes_seed`, and
`--seed` permutes them and draws the token ids, the memories and the
labels. Two seeds send the same multiset of requests. The times at which an
open loop's requests are due are the mix's own too (`arrival_offsets`).

Host-side numpy only; importing this touches no device.
"""
from __future__ import annotations

import numpy as np

# numpy's RandomState takes seeds below 2**32; the driver's are a little
# over 2**31, and mixing in a stream number must not overflow
_MASK = 0xFFFFFFFF


def rng(seed, stream=0):
    return np.random.RandomState((int(seed) * 1000003 + int(stream)) & _MASK)


def draw_lengths(spec, n, rs):
    """n whole numbers from the distribution a mix names, clipped to
    [min, max]. `lognormal` is parameterised by its median."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rs.lognormal(np.log(float(spec["median"])),
                         float(spec["sigma"]), n)
    elif spec["dist"] == "uniform":
        x = rs.uniform(lo, hi + 1, n)
    elif spec["dist"] == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def request_sizes(mix, seed):
    """[(prompt_len, new_tokens)] : the mix's fixed multiset, in the
    order this seed gives it."""
    rs = rng(mix["sizes_seed"], 1)
    n = int(mix["n_sizes"])
    sizes = np.stack([draw_lengths(mix["prompt_len"], n, rs),
                      draw_lengths(mix["new_tokens"], n, rs)], 1)
    return sizes[rng(seed, 2).permutation(n)]


class RequestMaker:
    """Requests of a serving mix: sizes cycle through `request_sizes`,
    token ids are new for every request (so nothing is shared unless the
    mix asks for a shared prefix), memories cycle through a few arrays
    made once, because a [M, D] normal draw per request would load the
    host that also runs the server."""

    def __init__(self, mix, seed, vocab, mem_shape, n_memories=8):
        self.sizes = request_sizes(mix, seed)
        self.vocab = int(vocab)
        self.rs = rng(seed, 3)
        self.memories = [
            self.rs.standard_normal(mem_shape).astype(np.float32)
            for _ in range(n_memories)]
        share = int(mix.get("shared_prefix", 0))
        self.prefix = self.rs.randint(2, self.vocab, (share,)).astype(
            np.int32)
        self.i = 0

    def next(self):
        p_len, n_new = self.sizes[self.i % len(self.sizes)]
        prompt = self.rs.randint(2, self.vocab, (int(p_len),)).astype(
            np.int32)
        k = min(len(self.prefix), int(p_len))
        prompt[:k] = self.prefix[:k]
        prompt[0] = 0   # bos, as chip_smoke.make_requests
        mem = self.memories[self.i % len(self.memories)]
        self.i += 1
        return prompt, mem, int(n_new)


def arrival_offsets(arrivals, seconds, stream=4):
    """Seconds after a span's start at which requests are due, for an
    open loop: `round(rate x seconds)` gaps drawn from the process the mix
    names with the mix's own `gaps_seed`, scaled so that they fill the span
    exactly. `--seed` has no part in it: a tail latency at four fifths of
    capacity is mostly queueing, and the 95th percentile of some 200
    queueing times under another order of the same gaps is another draw,
    with a standard error of about a tenth of itself, which no bound the
    contract allows could hold. The schedule is a parameter of the mix,
    like its rate; `stream` tells the warm, measured and traced spans
    apart."""
    rate = float(arrivals["rate_per_s"])
    n = max(1, int(round(rate * float(seconds))))
    rs = rng(arrivals["gaps_seed"], stream)
    if arrivals["process"] == "poisson":
        gaps = rs.exponential(1.0, n)
    elif arrivals["process"] == "gamma":      # bursty: cv > 1
        cv = float(arrivals["cv"])
        gaps = rs.gamma(1.0 / cv ** 2, cv ** 2, n)
    elif arrivals["process"] == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process "
                         f"{arrivals['process']!r}")
    gaps *= float(seconds) / gaps.sum()
    # the first arrival is due at the span's start, the last one gap
    # before its end
    return np.cumsum(gaps) - gaps


class BatchMaker:
    """Batches of a training job: [batch, seq_len] token ids and [batch]
    labels, a new batch every step, all from `--seed`."""

    def __init__(self, job, seed, vocab, num_classes):
        self.rs = rng(seed, 5)
        self.shape = (int(job["batch"]), int(job["seq_len"]))
        self.vocab, self.num_classes = int(vocab), int(num_classes)

    def next(self):
        ids = self.rs.randint(1, self.vocab, self.shape).astype(np.int64)
        labels = self.rs.randint(0, self.num_classes,
                                 (self.shape[0],)).astype(np.int64)
        return ids, labels
