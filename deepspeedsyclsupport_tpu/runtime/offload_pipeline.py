"""Bucketed offload pipeline primitives (ZeRO-Infinity style).

The bandwidth-centric pieces of the hierarchical offload engine
(``runtime/multihost_offload.py``), factored out so they are testable
without devices:

* :func:`plan_buckets` — partition the shard work-list into size-targeted
  buckets, coalescing small leaves (the reference's contiguous swap
  buffers, ``deepspeed/runtime/swap_tensor/optimizer_utils.py`` — transfer
  granularity is a buffer, never a tensor, so tiny leaves don't serialize
  the pipeline on per-request latency).
* :class:`OffloadStats` — per-step byte/seconds ledger for every tier
  (D2H grad pull, host compute, H2D master push, NVMe moment window) with
  the *exposed* stall separated from total transfer occupancy; overlap
  efficiency = 1 − exposed/total is the headline ``tools/trace_report.py``
  prints.
* :class:`ShardPull` — one async device→host grad-shard fetch
  (non-blocking ``jax.device_put`` to the host backend with a delayed
  wait) so every pull is in flight before anything blocks on it.
* :class:`MomentWindow` — a bounded double-buffered prefetch window of B
  buckets over :class:`~.swap_tensor.AsyncTensorSwapper`: moments are
  prefetched ahead of use, written back behind the compute, and the host
  copies dropped on retirement — host RAM high-water is bounded by the
  window, not the model (``ZeRO-Infinity`` §5; the old path prefetched
  the entire store up front).

Threading contract: worker threads touch numpy only; every jax call
(device_put, np.asarray of a jax array) stays on the caller's thread.
"""
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["BucketItem", "Bucket", "plan_buckets", "OffloadStats",
           "ShardPull", "MomentWindow", "overlap_efficiency",
           "DEFAULT_BUCKET_BYTES"]

#: default size-targeted bucket (coalesced small leaves) — the ONE
#: definition; ``OffloadConfig.bucket_size`` and the pipeline engine both
#: reference it.
DEFAULT_BUCKET_BYTES = 32 * 2 ** 20


def overlap_efficiency(stall_s: float, transfer_s: float) -> float:
    """1 − exposed/total transfer time, clamped to [0, 1] — THE canonical
    definition, shared by the per-step stats, the run summary and the
    Offload/* events (``tools/trace_report.py`` mirrors it inline: the
    offline tool loads no package modules). 1.0 means every byte moved
    entirely under compute; 0 means fully serial; no transfers counts as
    perfectly overlapped."""
    if transfer_s <= 0.0:
        return 1.0
    return min(1.0, max(0.0, 1.0 - stall_s / transfer_s))

#: (leaf_index, shard_key, nbytes) — one logical shard of one pytree leaf.
BucketItem = Tuple[int, str, int]


@dataclass(frozen=True)
class Bucket:
    """One pipeline unit: a contiguous run of shard items whose combined
    size targets the configured bucket bytes."""
    index: int
    items: Tuple[BucketItem, ...]
    nbytes: int


def plan_buckets(items: Sequence[BucketItem],
                 target_bytes: int) -> List[Bucket]:
    """Greedy size-targeted coalescing in leaf order (leaf order is the
    H2D first-use order). Small items pack together until the target is
    reached; an item at least as large as the target gets its own bucket
    (leaves are never split — shard granularity is the transfer unit)."""
    target_bytes = max(1, int(target_bytes))
    buckets: List[Bucket] = []
    cur: List[BucketItem] = []
    cur_bytes = 0

    def flush():
        nonlocal cur, cur_bytes
        if cur:
            buckets.append(Bucket(len(buckets), tuple(cur), cur_bytes))
            cur, cur_bytes = [], 0

    for item in items:
        nbytes = int(item[2])
        if cur_bytes and cur_bytes + nbytes > target_bytes:
            flush()
        cur.append(item)
        cur_bytes += nbytes
        if cur_bytes >= target_bytes:
            flush()
    flush()
    return buckets


def merged_span_length(spans: Sequence[Tuple[float, float]]) -> float:
    """Total length of the UNION of (start, end) intervals — transfer-busy
    wall time. Summing raw spans would double-count concurrent transfers
    (all pulls are issued up front, so their spans nest) and let a fully
    serial pipeline still report high overlap; the union is what the
    exposed stall is honestly compared against."""
    total = 0.0
    cur_start = cur_end = None
    for start, end in sorted(s for s in spans if s[1] > s[0]):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


@dataclass
class OffloadStats:
    """Per-step transfer/compute ledger.

    Every transfer interval is collected per direction and the ``*_s``
    occupancy values are the UNION of each direction's spans (concurrent
    pulls share one issue window — a sum would double-count them by the
    concurrency factor and understate effective GB/s; one convention for
    every direction). A span still covers any compute that ran under the
    transfer, so derived GB/s stays conservative. ``stall_s`` is the
    *exposed* time the step actually blocked waiting on a transfer — the
    number overlap exists to drive to zero; ``transfer_s`` (the all-
    direction union) is the denominator of overlap efficiency."""
    n_buckets: int = 0
    d2h_bytes: int = 0
    h2d_bytes: int = 0
    nvme_read_bytes: int = 0
    nvme_write_bytes: int = 0
    host_compute_s: float = 0.0
    stall_s: float = 0.0
    window_hwm_bytes: int = 0
    spans: List[Tuple[float, float]] = field(default_factory=list)
    dir_spans: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=dict)
    extra: Dict[str, Any] = field(default_factory=dict)

    def add_span(self, direction: str, start: float, end: float) -> None:
        self.spans.append((start, end))
        self.dir_spans.setdefault(direction, []).append((start, end))

    @property
    def d2h_s(self) -> float:
        return merged_span_length(self.dir_spans.get("d2h", ()))

    @property
    def h2d_s(self) -> float:
        return merged_span_length(self.dir_spans.get("h2d", ()))

    @property
    def nvme_read_s(self) -> float:
        return merged_span_length(self.dir_spans.get("nvme_read", ()))

    @property
    def transfer_s(self) -> float:
        """Transfer-busy wall time: union of all transfer spans across
        directions (NVMe writes are fire-and-forget through the swapper's
        aio queue — their backpressure surfaces as read stall, not a
        separate span)."""
        return merged_span_length(self.spans)

    @property
    def overlap_efficiency(self) -> float:
        """See :func:`overlap_efficiency` (the canonical definition)."""
        return overlap_efficiency(self.stall_s, self.transfer_s)

    def as_dict(self) -> Dict[str, Any]:
        d = {
            "n_buckets": self.n_buckets,
            "d2h_bytes": self.d2h_bytes, "h2d_bytes": self.h2d_bytes,
            "nvme_read_bytes": self.nvme_read_bytes,
            "nvme_write_bytes": self.nvme_write_bytes,
            "d2h_s": self.d2h_s, "h2d_s": self.h2d_s,
            "nvme_read_s": self.nvme_read_s,
            "host_compute_s": self.host_compute_s,
            "stall_s": self.stall_s,
            "transfer_s": self.transfer_s,
            "overlap_efficiency": self.overlap_efficiency,
            "window_hwm_bytes": self.window_hwm_bytes,
        }
        d.update(self.extra)
        return d

    def merge_into(self, totals: Dict[str, float]) -> None:
        """Accumulate this step's ledger into a running-totals dict."""
        for k, v in self.as_dict().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                if k in ("overlap_efficiency", "n_buckets",
                         "window_hwm_bytes"):
                    continue
                totals[k] = totals.get(k, 0.0) + v
        totals["window_hwm_bytes"] = max(
            totals.get("window_hwm_bytes", 0), self.window_hwm_bytes)
        totals["n_steps"] = totals.get("n_steps", 0) + 1


class ShardPull:
    """One async D2H grad-shard fetch: the ``jax.device_put`` to the host
    backend is issued at construction (non-blocking); :meth:`wait` is the
    single sanctioned blocking point and books exposed vs total time."""

    __slots__ = ("_fut", "_t_issue", "nbytes")

    def __init__(self, src: Any, host_device: Any):
        import jax

        self.nbytes = int(np.dtype(src.dtype).itemsize * np.prod(
            src.shape, dtype=np.int64)) if hasattr(src, "shape") else 0
        self._t_issue = time.perf_counter()
        self._fut = jax.device_put(src, host_device)

    def wait(self, stats: Optional[OffloadStats] = None) -> np.ndarray:
        t0 = time.perf_counter()
        arr = np.asarray(self._fut)
        t1 = time.perf_counter()
        if stats is not None:
            stats.stall_s += t1 - t0
            stats.d2h_bytes += self.nbytes
            stats.add_span("d2h", self._t_issue, t1)
        return arr


class MomentWindow:
    """Bounded prefetch window of Adam-moment buckets over the NVMe
    swapper.

    ``ensure(i)`` keeps buckets ``[i, i+window)`` in flight (reads issued,
    host buffers allocated); ``retrieve(i)`` blocks only on the tail of
    bucket *i*'s reads; ``retire(i)`` writes the updated moments back and
    drops every host reference — so at any instant at most ``window + 1``
    buckets of moments are host-resident (the window ahead plus the bucket
    whose write-back is being issued). ``hwm_bytes`` records the observed
    high-water and ``bound_bytes`` the contract it must stay under."""

    def __init__(self, swapper: Any, buckets: Sequence[Bucket],
                 window: int = 2):
        self.swapper = swapper
        self.buckets = list(buckets)
        self.window = max(1, int(window))
        self._next = 0
        #: bucket index -> {"t": issue time, "bytes": resident bytes,
        #:                  "mom": {(li, key): (m, v)} once retrieved}
        self._live: Dict[int, Dict[str, Any]] = {}
        self.resident_bytes = 0
        self.hwm_bytes = 0

    @property
    def bound_bytes(self) -> int:
        """The high-water contract: window+1 buckets of (m, v) pairs."""
        if not self.buckets:
            return 0
        biggest = max(b.nbytes for b in self.buckets)
        return (self.window + 1) * 2 * biggest

    @staticmethod
    def names(item: BucketItem) -> Tuple[str, str]:
        li, key, _ = item
        return f"m/{li}/{key}", f"v/{li}/{key}"

    def begin_step(self, stats: Optional[OffloadStats] = None) -> None:
        self._next = 0
        # re-stamp buckets surviving a skipped (overflow) step: their reads
        # completed long ago, and a span measured from the ORIGINAL issue
        # would book the whole skipped step as read occupancy — inflating
        # transfer_s and overstating overlap efficiency
        now = time.perf_counter()
        for info in self._live.values():
            info["t"] = now
        self.ensure(0, stats)

    def ensure(self, bi: int, stats: Optional[OffloadStats] = None) -> None:
        """Prefetch ahead so buckets ``[bi, bi+window)`` are in flight."""
        hi = min(max(bi + self.window, self._next), len(self.buckets))
        while self._next < hi:
            idx = self._next
            self._next += 1
            if idx in self._live:
                continue  # left in flight by a skipped (overflow) step
            b = self.buckets[idx]
            for item in b.items:
                for name in self.names(item):
                    self.swapper.prefetch(name)
            nbytes = 2 * b.nbytes
            self._live[idx] = {"t": time.perf_counter(), "bytes": nbytes}
            self.resident_bytes += nbytes
            self.hwm_bytes = max(self.hwm_bytes, self.resident_bytes)
            if stats is not None:
                stats.nvme_read_bytes += nbytes
                stats.window_hwm_bytes = self.hwm_bytes

    def retrieve(self, bi: int,
                 stats: Optional[OffloadStats] = None
                 ) -> Dict[Tuple[int, str], Tuple[np.ndarray, np.ndarray]]:
        """Block on bucket ``bi``'s prefetched reads; the wait is the
        exposed-stall ledger entry this window exists to minimize."""
        self.ensure(bi, stats)
        info = self._live[bi]
        t0 = time.perf_counter()
        mom: Dict[Tuple[int, str], Tuple[np.ndarray, np.ndarray]] = {}
        for item in self.buckets[bi].items:
            li, key, _ = item
            m_name, v_name = self.names(item)
            mom[(li, key)] = (self.swapper.retrieve(m_name),
                              self.swapper.retrieve(v_name))
        t1 = time.perf_counter()
        if stats is not None:
            stats.stall_s += t1 - t0
            stats.add_span("nvme_read", info["t"], t1)
        info["mom"] = mom
        return mom

    def retire(self, bi: int,
               stats: Optional[OffloadStats] = None) -> None:
        """Write the (updated-in-place) moments back and drop the host
        copies. The swapper retains each write buffer only until the write
        is confirmed durable (its retry contract), so retirement bounds
        OUR residency immediately."""
        info = self._live.pop(bi)
        mom = info.get("mom") or {}
        for item in self.buckets[bi].items:
            li, key, _ = item
            m, v = mom[(li, key)]
            m_name, v_name = self.names(item)
            self.swapper.swap_out(m_name, m)
            self.swapper.swap_out(v_name, v)
        self.resident_bytes -= info["bytes"]
        if stats is not None:
            stats.nvme_write_bytes += info["bytes"]
