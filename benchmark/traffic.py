"""The one general traffic generator: a mix is a data file, this reads it.

The rule for every serving mix: **a seed permutes and phases the work; it
does not resample it.** The mix's file states distributions of prompt and
output length; the generator takes them on a quantile grid (the i-th of n
lengths is the distribution's quantile at ``(i + 0.5) / n``), pairs prompts
with outputs by a fixed stride, and only then lets ``--seed`` shuffle the
order, draw the token ids and jitter the arrival phases. Two seeds therefore
offer the same multiset of (prompt, output) lengths, and the same prompt and
output tokens per second.

Kinds (``"kind"`` in the mix's file):

* ``closed`` — ``clients`` callers, each sending its next request the
  instant its last one finished; requests cycle through a grid of ``count``
  pairs, reshuffled each cycle and dealt to whichever caller asks next.
  With ``order_block`` a cycle's order is stratified as an open loop's is
  (``spread_order``): for a deck of which a window sees a fraction, where
  the shuffle decides which prompts that fraction holds.
  With ``"order": "lanes"`` every caller walks the whole grid on ONE fixed
  walk and the seed only deals the callers their places on it: for a mix
  whose window holds about one cycle, where the shuffle decides whose long
  prompts fall inside it (``ClosedPlan``).
* ``open-fixed-rate`` — arrivals every ``1 / rate_per_s`` seconds whatever
  the server does, each moved by a seeded jitter of at most
  ``jitter_gaps`` (<= 0.5) of a gap; a ramp of ``ramp_seconds`` before the
  window, then ``round(rate * seconds)`` arrivals inside it: one whole grid,
  in an order stratified over stretches of ``order_block`` arrivals.
* ``train-batches`` — fresh seeded LM batches, one per step.
"""
import math
from statistics import NormalDist

import numpy as np

_N01 = NormalDist()


def quantile(dist, u):
    """The ``u``-quantile (0 < u < 1) of a length distribution, as an int."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif dist["dist"] == "lognormal":
        # truncated to [min, max]: the grid never leaves the stated range
        mu, s = math.log(dist["median"]), dist["sigma"]
        fa = _N01.cdf((math.log(lo) - mu) / s)
        fb = _N01.cdf((math.log(hi) - mu) / s)
        x = math.exp(mu + s * _N01.inv_cdf(fa + u * (fb - fa)))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(hi, max(lo, round(x))))


def length_pairs(mix, n):
    """The mix's fixed multiset of ``n`` (prompt, output) lengths, in grid
    order. Outputs are paired with prompts by a fixed stride coprime to
    ``n`` (near ``0.618 n``), so long prompts do not all get long answers
    and no seed is involved."""
    grid = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(mix["prompt_len"], u) for u in grid]
    outputs = [quantile(mix["output_len"], u) for u in grid]
    stride = coprime_stride(n, 0.618)
    return [(prompts[i], outputs[(i * stride) % n]) for i in range(n)]


def coprime_stride(n, share):
    """The stride nearest ``share * n`` from above that is coprime to ``n``:
    ``(i * stride) % n`` then visits all of ``0..n-1``, far apart first."""
    stride = max(1, round(share * n))
    while math.gcd(stride, n) != 1:
        stride += 1
    return stride


def offered_per_s(mix, seconds):
    """(prompt tokens, output tokens) the mix offers per second. For an
    open loop over a window of ``seconds``; for a closed loop per request
    (the server's own pace sets the rate)."""
    if mix["kind"] == "open-fixed-rate":
        pairs = length_pairs(mix, window_count(mix, seconds))
        return (sum(p for p, _ in pairs) / seconds,
                sum(o for _, o in pairs) / seconds)
    pairs = length_pairs(mix, mix["count"])
    return (sum(p for p, _ in pairs) / len(pairs),
            sum(o for _, o in pairs) / len(pairs))


def window_count(mix, seconds):
    return max(1, round(mix["rate_per_s"] * seconds))


def _request(rng, vocab, prompt_len, output_len):
    return {"tokens": rng.integers(0, vocab, prompt_len).tolist(),
            "max_new_tokens": int(output_len)}


class ClosedPlan:
    """Requests for a closed loop, drawn without end.

    Default: each cycle is the whole grid in a fresh seeded order, dealt to
    whichever caller asks next. Over a window of many cycles every seed's
    window holds the same work. Where a window sees a FRACTION of a cycle,
    ``order_block`` stratifies the order (``spread_order``): every stretch
    of that many requests holds one of each class of neighbouring prompt
    lengths, so any window's stretches send about the same prompt tokens
    under every seed. A cycle still deals every pair once.

    ``"order": "lanes"``: for a mix whose window holds about ONE cycle, where
    the shuffle decides whether one or two of the longest prompts end inside
    it (``video-32k-sat``: six seeds spread 4-6 % on the tail of the gaps,
    one seed twice 0.3 %). Every caller sends the WHOLE grid, in one fixed
    walk (grid index ``k * stride % count``, ``stride`` the coprime nearest
    ``0.382 count``: a caller's consecutive prompts lie far apart in length),
    and the callers stand at evenly spaced places on that walk, dealt by the
    seed. Whenever the loop is looked at, the requests in flight are the grid
    spread over the callers, and any stretch in which every caller ends one
    request holds the whole grid once, whichever seed and wherever the window
    falls. What a seed still draws: which caller stands where, and every
    token id."""

    def __init__(self, mix, seed, vocab):
        self.pairs = length_pairs(mix, mix["count"])
        self.clients = int(mix["clients"])
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self._cycle = []
        self._place = None
        self._block = mix.get("order_block")
        order = mix.get("order", "shuffle")
        if order == "lanes":
            n = len(self.pairs)
            stride = coprime_stride(n, 0.382)
            self._walk = [(k * stride) % n for k in range(n)]
            self._place = [int(c) * n // self.clients
                           for c in self.rng.permutation(self.clients)]
        elif order != "shuffle":
            raise ValueError(f"unknown closed-loop order {order!r}")

    def take(self, client):
        """Caller ``client``'s next request (a shuffled deck does not look
        at who asks)."""
        if self._place is None:
            if not self._cycle:
                order = spread_order(self.rng, len(self.pairs), self._block)
                self._cycle = [_request(self.rng, self.vocab, *self.pairs[i])
                               for i in order[::-1]]
            return self._cycle.pop()
        i = self._walk[self._place[client] % len(self._walk)]
        self._place[client] += 1
        return _request(self.rng, self.vocab, *self.pairs[i])


def spread_order(rng, n, block=None):
    """A seeded order of grid indices ``0..n-1`` (sorted by prompt length).
    With ``block``, the order is stratified: the grid is cut into classes
    of neighbouring lengths, and every stretch of about ``block`` arrivals
    gets one request of each class, in a seeded order, so the long prompts
    are spread over the window under every seed instead of clustering
    under some. Without it, a plain shuffle."""
    if not block or block >= n:
        return [int(i) for i in rng.permutation(n)]
    stretches = max(1, round(n / block))
    slots = [[] for _ in range(stretches)]
    for lo in range(0, n, stretches):          # one class of lengths
        members = list(range(lo, min(lo + stretches, n)))
        for stretch, i in zip(rng.permutation(stretches), members):
            slots[stretch].append(i)
    return [int(i) for stretch in slots
            for i in rng.permutation(stretch)]


def open_arrivals(mix, seed, seconds, vocab, extra_seconds=0):
    """Every request of an open-loop run as ``{"due": seconds from the
    window's start (negative in the ramp), "tokens", "max_new_tokens"}``,
    in due order. The window's requests are one whole grid of
    ``round(rate * seconds)`` pairs and the ramp's another: what a seed
    changes is their order, their ids and their phases. (The window is
    ``round(rate * seconds)`` gaps long: give ``seconds`` a whole number
    of gaps, as every whole number is at the rates in use.)"""
    rng = np.random.default_rng(seed)
    gap = 1.0 / mix["rate_per_s"]
    jitter = float(mix.get("jitter_gaps", 0.0))
    if not 0.0 <= jitter <= 0.5:
        raise ValueError(f"jitter_gaps must be within [0, 0.5], got {jitter}")
    n_ramp = max(1, round(mix["ramp_seconds"] * mix["rate_per_s"]))
    n_win = window_count(mix, seconds)
    phases = [(n_ramp, -n_ramp), (n_win, 0)]
    if extra_seconds:   # a traced run's tail, after the window: a grid more
        phases.append((max(1, round(extra_seconds * mix["rate_per_s"])),
                       n_win))
    out = []
    for n, first in phases:
        pairs = length_pairs(mix, n)
        for slot, i in enumerate(spread_order(rng, n, mix.get("order_block"))):
            phase = 0.5 + jitter * rng.uniform(-1.0, 1.0)
            out.append({"due": (first + slot + phase) * gap,
                        **_request(rng, vocab, *pairs[i])})
    return out


def pattern_batches(seed, batch, seq, vocab):
    """Fresh seeded LM batches without end, with learnable structure: every
    row is an arithmetic progression over the first ``vocab // 4`` tokens, so
    the loss visibly falls over a window (uniform noise has nothing to
    learn, a repeated batch would only show memorisation). Copied from
    ``chip_smoke.pattern_batches`` (the smoke may change; the yardstick may
    not)."""
    rng = np.random.default_rng(seed)
    support = max(8, vocab // 4)
    while True:
        start = rng.integers(0, support, (batch, 1))
        stride = rng.integers(1, 4, (batch, 1))
        ids = (start + stride * np.arange(seq)[None, :]) % support
        yield {"input_ids": ids.astype(np.int32)}
