"""SLA-aware serving policy layer above :class:`InferenceEngineV2`.

The scheduling policy the "Ragged Paged Attention" stack assumes sits above
the paged KV cache (PAPERS.md): the engine below this module is a batch
executor — it will happily admit everyone and let everyone miss deadline.
This layer makes overload degrade *gracefully* instead:

* **admission control** — every request carries a deadline budget (TTFT
  bound + decode token-rate SLA, stamped onto its
  :class:`~.ragged.SequenceDescriptor`); an EWMA :class:`CapacityModel` of
  measured prefill tok/s and decode step time projects each arrival's
  completion, and the gate admits, queues, or *sheds* it so that admitting
  never blows the SLA of already-admitted streams;
* **deadline-driven batch composition** — admitted work is ordered by
  slack (:func:`~.scheduler.slack_of`) with starvation aging and a
  per-tenant prefill budget per round (:class:`~.scheduler.SlackPolicy`);
* **overload-graceful eviction** — when the paged KV pool exhausts, the
  lowest-slack stream is preempted (`engine.preempt`: blocks freed,
  request rejected with partial output or requeued) rather than stalling
  the whole batch.

Everything here is host-side policy over monotonic time
(``time.perf_counter``); the ``clock`` hook exists so tests drive the
policy with a synthetic clock and capacity model. See ``docs/serving.md``
for the overload-behavior contract and config keys.
"""
import contextlib
import heapq
import math
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from .config import ServingPolicyConfig
from .kv_cache import kv_pool_stats
from .scheduler import SlackPolicy, slack_of
from ..sampling import SamplingParams, split_key
from ...comm.watchdog import SERVE_HANG_EXIT_CODE, CollectiveWatchdog
from ...monitor.reqtrace import (FORWARD_FIELDS, NO_PHASE, ROUND_PHASES,
                                 check_phase)
from ...utils.fault_injection import get_fault_injector
from ...utils.logging import logger

#: ``Serve/*`` metric names this module emits (registered in
#: ``monitor.telemetry.EVENT_NAMES`` so ``DSTPU_STRICT_EVENTS=1`` passes).
SERVE_COUNTERS = ("Serve/admitted", "Serve/queued", "Serve/shed",
                  "Serve/evicted", "Serve/completed")
#: sliding-window SLO burn gauges (request-time attribution,
#: docs/observability.md): TTFT-SLA miss fraction, shed fraction, and
#: max(miss, shed)/error-budget burn rate over ``policy.slo_window_s``
SERVE_SLO_GAUGES = ("Serve/slo.ttft_miss_frac", "Serve/slo.shed_frac",
                    "Serve/slo.burn_rate")
SERVE_GAUGES = ("Serve/queue_depth", "Serve/kv_occupancy",
                "Serve/live_seqs") + SERVE_SLO_GAUGES
#: ``Serve/queue_wait_s`` is the satellite admission→prefill-dispatch wait;
#: the ``Serve/stage.*_s`` pair are the per-request prefill/decode phase
#: self-times observed at close — all surface p50/p95/p99 via
#: :meth:`ServingSession.summary_events` (quantile members are registry-
#: enumerated in ``monitor/telemetry.py``)
SERVE_HISTOGRAMS = ("Serve/ttft_s", "Serve/itl_s", "Serve/queue_wait_s",
                    "Serve/stage.prefill_s", "Serve/stage.decode_s",
                    "Serve/recovery.time_to_recover_s")
#: crash-replay recovery family (``inference/v2/supervisor.py`` — journal
#: replay counters + the stuck-decode watchdog's abort count). Full
#: literals on purpose: the static event-name lint resolves each against
#: the registry.
_RECOVERY_COUNTERS = {"replays": "Serve/recovery.replays",
                      "replay_sheds": "Serve/recovery.replay_sheds"}
SERVE_RECOVERY = (_RECOVERY_COUNTERS["replays"],
                  _RECOVERY_COUNTERS["replay_sheds"],
                  "Serve/recovery.serve_hang_aborts")
#: cross-request prefix cache (``inference/v2/prefix_cache.py`` —
#: docs/serving.md "prefix reuse"). Full literals on purpose: the static
#: event-name lint resolves each against the registry.
_PREFIX_COUNTERS = {"hits": "Serve/prefix.hits",
                    "misses": "Serve/prefix.misses",
                    "tokens_saved": "Serve/prefix.tokens_saved",
                    "blocks_shared": "Serve/prefix.blocks_shared",
                    "cow_copies": "Serve/prefix.cow_copies"}
SERVE_PREFIX = (_PREFIX_COUNTERS["hits"], _PREFIX_COUNTERS["misses"],
                _PREFIX_COUNTERS["tokens_saved"],
                _PREFIX_COUNTERS["blocks_shared"],
                _PREFIX_COUNTERS["cow_copies"],
                "Serve/prefix.hit_ratio", "Serve/prefix.pinned_blocks")
SERVE_EVENT_NAMES = (SERVE_COUNTERS + SERVE_GAUGES + SERVE_HISTOGRAMS
                     + SERVE_RECOVERY + SERVE_PREFIX)


class Ewma:
    """Exponentially-weighted moving average seeded with a prior; the first
    measured sample replaces the prior outright (a prior is a guess, not
    data — blending it in would drag measurements toward it for ~1/alpha
    samples)."""

    __slots__ = ("value", "alpha", "samples")

    def __init__(self, prior: float, alpha: float = 0.25):
        self.value = float(prior)
        self.alpha = float(alpha)
        self.samples = 0

    def update(self, x: float) -> float:
        x = float(x)
        self.value = x if self.samples == 0 else \
            (1.0 - self.alpha) * self.value + self.alpha * x
        self.samples += 1
        return self.value


class CapacityModel:
    """Measured service capacity: prefill tokens/s and decode seconds/step.

    The engine's forwards are shape-padded (every decode dispatch computes
    ``max_sequences`` slots), so decode step time is close to
    occupancy-independent — one EWMA per quantity captures it; the
    admission gate multiplies by ``sla_headroom`` instead of modelling the
    residual occupancy sensitivity.
    """

    def __init__(self, prefill_tok_s: float = 1000.0,
                 decode_step_s: float = 0.05, alpha: float = 0.25):
        self._prefill = Ewma(prefill_tok_s, alpha)
        self._step = Ewma(decode_step_s, alpha)
        # best-case (least-loaded) rates ever measured: what an IDLE engine
        # delivers. The EWMA deliberately folds queueing delay in (that is
        # the backpressure signal), which makes it an over-estimate of
        # service time on an empty engine — and once everything is shed no
        # new samples arrive, so gating an idle engine on the loaded EWMA
        # is an absorbing shed-everything state.
        self._prefill_best = 0.0
        self._step_best = math.inf

    # ------------------------------------------------------------- recording
    def record_prefill(self, tokens: int, seconds: float) -> None:
        if tokens > 0 and seconds > 0:
            sample = tokens / seconds
            rate = self._prefill.update(sample)
            # best only rises when the smoothed rate supports the sample:
            # one spuriously fast outlier must not pin the idle-engine
            # projection optimistic forever
            self._prefill_best = max(self._prefill_best, min(rate, sample))

    def record_decode(self, steps: int, seconds: float) -> None:
        if steps > 0 and seconds > 0:
            sample = seconds / steps
            step = self._step.update(sample)
            # symmetric outlier guard (see record_prefill)
            self._step_best = min(self._step_best, max(step, sample))

    # ------------------------------------------------------------- estimates
    @property
    def prefill_tok_s(self) -> float:
        return max(self._prefill.value, 1e-9)

    @property
    def prefill_tok_s_best(self) -> float:
        """Best-case prefill rate: for projecting service on an idle
        engine (falls back to the EWMA/prior before any measurement)."""
        return max(self._prefill_best, self.prefill_tok_s)

    @property
    def decode_step_s(self) -> float:
        return max(self._step.value, 1e-9)

    @property
    def decode_step_s_best(self) -> float:
        return max(min(self._step_best, self.decode_step_s), 1e-9)

    @property
    def decode_tok_s(self) -> float:
        """Per-stream decode rate (1 token per live stream per step)."""
        return 1.0 / self.decode_step_s

    @property
    def decode_tok_s_best(self) -> float:
        return 1.0 / self.decode_step_s_best

    def prefill_eta_s(self, tokens: int, best: bool = False) -> float:
        return tokens / (self.prefill_tok_s_best if best
                         else self.prefill_tok_s)


class _Phase:
    """One phase's context manager, made once per ``RoundSpans`` and
    re-entered every round: a round passes a dozen of these, so entering
    one is two clock reads, a list append and the profiler's annotation."""

    __slots__ = ("spans", "name", "label", "open")

    def __init__(self, spans: "RoundSpans", name: str):
        self.spans, self.name = spans, name
        self.label = "dstpu/serve/" + name
        self.open: List[Any] = []   # annotations entered and not yet left

    def __enter__(self) -> None:
        self.spans._charge()
        self.spans._stack.append(self.name)
        annotation = jax.profiler.TraceAnnotation(self.label)
        annotation.__enter__()
        self.open.append(annotation)

    def __exit__(self, *exc) -> None:
        self.open.pop().__exit__(*exc)
        self.spans._charge()
        self.spans._stack.pop()


class RoundSpans:
    """The phase clock of the running scheduling round
    (``reqtrace.ROUND_PHASES``; docs/observability.md "Round phases").

    ``with spans.phase(name):`` charges the block's time on the session's
    clock to ``name``, less what a phase entered inside it claims, and opens
    ``dstpu/serve/<name>`` in the profiler's trace, nested in the
    ``dstpu/serve/round`` annotation :meth:`round` opened: under any
    ``jax.profiler`` trace the same spans sit beside the device's timeline.
    Time no phase claims is ``other``, so ``phases`` partitions
    ``[t0, t1]``. ``fields`` collects what the round's ``round`` record says
    besides: the session notes the sampled uids, the engine
    (handed this object for the round as ``engine.round_spans``) the program
    it launched and what that forward covered."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self._phases = {name: _Phase(self, name) for name in ROUND_PHASES}

    @contextlib.contextmanager
    def round(self, number: int, t0: float):
        """Round ``number``, which began at ``t0`` of the session's clock."""
        self.t0 = self._mark = t0
        self.t1: Optional[float] = None
        self.phases: Dict[str, float] = {}
        self.fields: Dict[str, Any] = {}
        self._stack = ["other"]
        with jax.profiler.TraceAnnotation("dstpu/serve/round", round=number):
            try:
                yield
            finally:
                self._charge()
                self.t1 = self._mark

    def _charge(self) -> None:
        """Close the running phase's stretch at the clock's next reading."""
        now = self.clock()
        name = self._stack[-1]
        self.phases[name] = self.phases.get(name, 0.0) + now - self._mark
        self._mark = now

    def phase(self, name: str) -> _Phase:
        if name not in self._phases:
            check_phase(name)      # raises: not in the registry
        return self._phases[name]

    def launched(self, program: str) -> None:
        """The engine's ``_dispatch`` of ``program`` has just returned."""
        self.fields["program"] = program
        self.fields["launch_t"] = self.clock()


@dataclass
class ServeEvent:
    """One observable serving outcome, stamped on the session clock.

    kinds: ``token`` (``tokens`` delivered at ``t``), ``finish`` (reason:
    done|eos|context|evicted), ``shed`` (admission rejected the request;
    reason names why), ``evict`` (KV-pressure preemption; reason:
    reject|requeue).
    """

    kind: str
    uid: int
    t: float
    tokens: List[int] = field(default_factory=list)
    reason: str = ""


@dataclass
class _Request:
    uid: int
    tokens: List[int]
    max_new_tokens: int
    tenant: str
    arrival_s: float
    deadline_s: Optional[float]
    rate_sla: float
    budget: int = 0                 # remaining new-token budget
    out: List[int] = field(default_factory=list)  # emitted tokens (requeue)
    enqueue_s: float = 0.0          # when the prompt entered the engine
    queued_s: float = 0.0           # when it (last) entered the queue
    cached_prefix_len: int = 0      # prefix-cache hit at (last) activation
    preempted: bool = False         # next activation is a requeue, not fresh
    #: ``tokens`` stays the ORIGINAL prompt forever; a requeued stream's
    #: context is rebuilt as tokens + out at activation (mutating tokens
    #: would duplicate the partial output on a second eviction)

    @property
    def n_prefill(self) -> int:
        """Tokens a (re)admission must prefill: prompt + emitted prefix."""
        return len(self.tokens) + len(self.out)
    first_token_s: Optional[float] = None
    last_emit_s: Optional[float] = None


class ServingSession:
    """Drives one engine under the SLA policy; the serving loop an MII-style
    frontend (or the benchmark harness's load loop) sits on.

    ``submit()`` is the admission gate; ``step()`` runs one scheduling
    round — queue maintenance, slack-ordered batch composition, the
    per-token dispatch, KV-pressure eviction — and returns the round's
    :class:`ServeEvent` stream. The caller owns pacing (when to call
    ``step``) and delivery; the session owns policy.

    A per-token round launches the sampler over the last forward's logits,
    then the NEXT forward, and only then reads the sampled tokens back
    (:meth:`_per_token_round`). In between the token values belong to the
    DEVICE (``engine_v2.SampledTokens``): the session hands them to ``put``
    by reference, keeps ``_pending_tok`` references for what was refused,
    and writes the values over them at the read-back. Nothing stays unread
    when ``step()`` returns: token N and a ``finish`` are returned by the
    ``step()`` after forward N's launch, as a round that read first returned
    them, and at most ONE forward is in flight ahead of a read-back.
    """

    def __init__(self, engine, policy: Optional[ServingPolicyConfig] = None,
                 *, clock: Callable[[], float] = time.perf_counter,
                 capacity: Optional[CapacityModel] = None,
                 sampling: Optional[SamplingParams] = None,
                 eos_token_id: Optional[int] = None,
                 rng: Optional[jax.Array] = None,
                 journal: Any = None, watchdog: Any = None):
        self.eng = engine
        self.policy = policy or ServingPolicyConfig()
        self.clock = clock
        self.capacity = capacity or CapacityModel(
            self.policy.prefill_tok_s_prior, self.policy.decode_step_s_prior,
            self.policy.ewma_alpha)
        self.sampling = sampling or SamplingParams()
        self.eos_token_id = eos_token_id
        self.queue: List[_Request] = []
        self.running: Dict[int, _Request] = {}
        self.counters: Dict[str, int] = {
            "admitted": 0, "queued": 0, "shed": 0, "evicted": 0,
            "completed": 0}
        #: crash-replay recovery accounting (``Serve/recovery.*`` family)
        self.recovery_counters: Dict[str, int] = {"replays": 0,
                                                  "replay_sheds": 0}
        # sampled, not yet submitted: the token, or inside a round a
        # reference to it while the device still has it (ragged.device_token)
        self._pending_tok: Dict[int, int] = {}
        self._launched_ahead = 0   # forwards launched before a read-back
        self._spec_rows = 0        # rows launched for a stream that had ended
        self._last_decode_s: Optional[float] = None
        self._round = 0            # scheduling rounds (watchdog step label)
        self._tokens_emitted = 0   # serve_crash fault trigger input
        self._stall_rounds = 0     # consecutive no-progress rounds
        # request-time attribution (monitor/reqtrace.py; docs/
        # observability.md): lifecycle-edge records mirrored into a bounded
        # in-memory ring so a caller joins per-request waterfalls with
        # zero disk IO in the measured path (the journal — when
        # configured — carries the same records durably). The fixed wall
        # offset maps this session's monotonic clock onto the journal's
        # wall stamps: every record rides ONE clock base, so the offline
        # join can order router and replica streams together.
        self._tracing = bool(self.policy.trace_stages)
        # a record a token and six a request: 65,536 held under a minute at
        # 1,150 tokens/s. The one record a ROUND has a ring of its own: at
        # 256 live a round leaves 257 records, the stream's ring turns over
        # in ~1,000 rounds, and a window whose oldest rounds were pushed
        # out gives the round readers nothing (benchmark/spans.py)
        self.trace_log: deque = deque(maxlen=262144)
        self.round_log: deque = deque(maxlen=32768)
        self.trace_dropped = 0     # records a full ring pushed out
        self._round_spans = RoundSpans(clock) if self._tracing else None
        self._spans: Optional[RoundSpans] = None   # the running round's
        self._wall0 = time.time() - self.clock()  # dslint: allow(wall-clock-in-step-path)
        # SLO burn accounting (Serve/slo.* gauges): sliding windows of
        # (t, first-token-met-SLA) and (t, outcome-was-shed) samples
        self._slo_ttft: deque = deque()
        self._slo_gate: deque = deque()
        self._rng = rng if rng is not None else \
            jax.random.PRNGKey(engine.config.seed + 1)
        # cross-request prefix reuse (docs/serving.md "prefix reuse"): the
        # policy owns the knobs, the engine owns the cache — installing is
        # idempotent, so a recovered session reuses the warm index
        pc_cfg = self.policy.prefix_cache
        if pc_cfg and pc_cfg.get("enabled", True):
            engine.install_prefix_cache(
                scope=pc_cfg.get("scope", "tenant"),
                min_block_hits=int(pc_cfg.get("min_block_hits", 1)),
                max_pinned_blocks=pc_cfg.get("max_pinned_blocks"))
        # registry counters are monotone increments; the cache keeps plain
        # totals — this snapshot turns totals into deltas at flush time
        self._prefix_reported: Dict[str, int] = {}
        if self.policy.telemetry:
            from ...monitor.telemetry import metrics_registry as _mr

            self._metrics = _mr
        else:
            self._metrics = None
        # request journal: in-flight state survives the process (see
        # docs/serving.md "failure contract"); caller-provided instance
        # wins over the config path
        if journal is None and self.policy.journal_path:
            from .supervisor import RequestJournal

            journal = RequestJournal(self.policy.journal_path)
        self.journal = journal
        # stuck-decode watchdog: the collective watchdog's machinery with
        # the serving contract's names — rc 219, serve_hang_aborts, and
        # serve/arm-serve/hang deadline records into the journal stream
        if watchdog is None and self.policy.watchdog_enabled:
            watchdog = CollectiveWatchdog(
                deadline_s=self.policy.watchdog_deadline_s,
                warmup_deadline_s=self.policy.watchdog_warmup_deadline_s,
                poll_s=self.policy.watchdog_poll_s,
                telemetry=self.journal,
                exit_code=SERVE_HANG_EXIT_CODE,
                abort_counter="serve_hang_aborts",
                arm_name="serve/arm", hang_name="serve/hang",
                what="serving decode").start()
        self.watchdog = watchdog

    def close(self) -> None:
        """Stop the watchdog poller and close the journal stream.
        Idempotent; live/queued requests stay journaled as in-flight (the
        truthful state for a replica being stopped mid-serve)."""
        if self.watchdog is not None:
            try:
                self.watchdog.stop()
            except Exception:  # teardown must never raise out of serving
                pass
        if self.journal is not None:
            self.journal.close()

    # ----------------------------------------------- request-time attribution
    def _trace(self, name: str, t: float, data: Dict[str, Any],
               ring: Optional[deque] = None) -> None:
        """Mirror one lifecycle record (journal-record shape) into the
        in-memory ring (``ring``: the rounds'), stamped on the
        session-clock→wall mapping."""
        if self._tracing:
            ring = self.trace_log if ring is None else ring
            if len(ring) == ring.maxlen:
                self.trace_dropped += 1
            ring.append({"name": name, "t": t + self._wall0, "data": data})

    def _stage(self, uid: int, stage: str, t: float,
               dur: Optional[float] = None, **data: Any) -> None:
        """``serve/stage`` lifecycle edge: in-memory ring always (when
        tracing), journal stream when one is configured — same record, one
        clock base, no second transport."""
        if not self._tracing:
            return
        self._trace("serve/stage", t, {
            "uid": int(uid), "stage": stage,
            **({"dur": float(dur)} if dur is not None else {}), **data},
            self.round_log if stage == "round" else None)
        if self.journal is not None:
            self.journal.stage(uid, stage, dur=dur, **data)

    def note_stage(self, uid: int, stage: str,
                   dur: Optional[float] = None, **data: Any) -> None:
        """Public stamping hook for the owning loop (``serve_worker``
        stamps ``spool_wait`` through this; a future RPC front-end stamps
        its ingress edge the same way)."""
        self._stage(uid, stage, self.clock(), dur=dur, **data)

    def _phase(self, name: str):
        """The running round's span around ``name``
        (``reqtrace.ROUND_PHASES``); nothing when ``trace_stages`` is off or
        the round began with no work."""
        return NO_PHASE if self._spans is None else self._spans.phase(name)

    def _stamp_round(self, spans: RoundSpans) -> None:
        """The round's one record: when it ran (``t0``/``t1``/``launch_t``
        on the SESSION's clock — the record's own ``t`` is shifted onto the
        wall), what it dispatched and what that forward covered
        (``reqtrace.FORWARD_FIELDS``), and where its time went (``phases``
        sums to ``t1 - t0``). ``uids`` is what the join fans out to each
        request's round count."""
        self._stage(-1, "round", spans.t1, **{
            "round": self._round, "t0": spans.t0, "t1": spans.t1,
            "launch_t": None, "program": None,
            "uids": [], **dict.fromkeys(FORWARD_FIELDS, 0),
            **spans.fields, "phases": dict(spans.phases)})

    def drain_trace(self) -> List[Dict[str, Any]]:
        """Hand over and clear the in-memory lifecycle records, both rings
        as one stream in the order of ``t`` — the benchmark harness drains
        once per window so the waterfall joins only that window's
        requests."""
        out = list(heapq.merge(self.trace_log, self.round_log,
                               key=lambda rec: rec["t"]))
        self.trace_log.clear()
        self.round_log.clear()
        return out

    def export_metrics(self, path: str) -> Optional[str]:
        """Prometheus textfile snapshot of the session's registry (atomic
        rename — the training exporter's contract). No-op without
        telemetry."""
        if self._metrics is None:
            return None
        from ...monitor.telemetry import export_metrics_textfile

        return export_metrics_textfile(path, self._metrics.snapshot())

    def _slo_snapshot(self, now: float) -> Tuple[float, float, float]:
        """(ttft_miss_frac, shed_frac, burn_rate) over the trailing
        ``policy.slo_window_s`` window. Burn is the worse of the two miss
        fractions priced against the error budget: burn > 1 means the SLO
        budget is being spent faster than it accrues."""
        horizon = now - self.policy.slo_window_s
        for dq in (self._slo_ttft, self._slo_gate):
            while dq and dq[0][0] < horizon:
                dq.popleft()
        miss = (1.0 - sum(1 for _, ok in self._slo_ttft if ok)
                / len(self._slo_ttft)) if self._slo_ttft else 0.0
        shed = (sum(1 for _, s in self._slo_gate if s)
                / len(self._slo_gate)) if self._slo_gate else 0.0
        burn = max(miss, shed) / max(self.policy.slo_budget, 1e-9)
        return miss, shed, burn

    # ------------------------------------------------------------- admission
    def submit(self, uid: int, tokens: Sequence[int], max_new_tokens: int,
               *, tenant: str = "default", now: Optional[float] = None,
               ttft_sla_s: Optional[float] = None,
               rate_sla: Optional[float] = None) -> str:
        """Admission gate. Returns ``"admitted"`` (prompt enqueued for the
        next round), ``"queued"`` (held; re-evaluated every round), or
        ``"shed"`` (rejected now — the graceful-overload answer: the client
        learns in O(1) instead of timing out)."""
        if not tokens:
            raise ValueError("cannot serve an empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if uid in self.running or uid in self.eng.seqs \
                or any(r.uid == uid for r in self.queue):
            raise ValueError(f"uid {uid} is already being served")
        now = self.clock() if now is None else now
        ttft = ttft_sla_s if ttft_sla_s is not None else self.policy.ttft_sla_s
        req = _Request(
            uid=uid, tokens=list(tokens), max_new_tokens=int(max_new_tokens),
            tenant=tenant, arrival_s=now,
            deadline_s=(now + ttft) if ttft is not None else None,
            rate_sla=(rate_sla if rate_sla is not None
                      else self.policy.token_rate_sla),
            budget=int(max_new_tokens), queued_s=now)
        decision = self._gate(req, now, ahead_tokens=self._queued_tokens())
        if decision == "admit" and self.queue:
            # no leapfrogging: a new arrival must not take a freed slot
            # ahead of older queued requests — it joins the queue, which
            # _maintain_queue re-gates in deadline order every round (an
            # urgent arrival still legitimately outranks laxer ones there)
            decision = "queue"
        # gate-verdict edge: the ONLY trace a shed-at-submit request leaves
        # (terminal sheds are never journaled as admits), so the waterfall
        # still counts and names them
        self._stage(uid, "gate", now, verdict=decision,
                    n_prompt=len(req.tokens))
        self._slo_gate.append((now, decision == "shed"))
        if decision == "shed":
            # terminal at submit: the caller learns synchronously, nothing
            # is in flight — so nothing to journal
            self._count("shed")
            return "shed"
        if self.journal is not None:
            # journaled BEFORE any token can be produced: from here the
            # request is in flight and must survive the process
            self.journal.admit(uid, req.tokens, req.max_new_tokens,
                               tenant=req.tenant, rate_sla=req.rate_sla,
                               ttft_sla_s=ttft)
        self._trace("serve/admit", now, {
            "uid": int(uid), "n_tokens": len(req.tokens),
            "max_new_tokens": req.max_new_tokens, "tenant": req.tenant,
            "rate_sla": req.rate_sla,
            **({"ttft_sla_s": float(ttft)} if ttft is not None else {})})
        if decision == "admit":
            self._activate(req, now)
            return "admitted"
        self.queue.append(req)
        self._count("queued")
        return "queued"

    def replay(self, uid: int, tokens: Sequence[int], max_new_tokens: int,
               *, emitted_tokens: Sequence[int] = (),
               tenant: str = "default", rate_sla: Optional[float] = None,
               now: Optional[float] = None) -> str:
        """Re-admit a journaled in-flight request from its emitted-token
        watermark after an engine death (``supervisor.recover_requests``).

        The TTFT deadline is burned (the first token — if any — was
        delivered in a previous incarnation), so the gate re-projects on
        the **rate SLA only**, exactly like PR 4's requeue path; the
        context is rebuilt as prompt + emitted prefix at activation, so
        the stream continues from the watermark with zero duplicate
        tokens. Returns ``"replayed"`` (re-admitted or queued),
        ``"shed"`` (provably unmeetable — terminal, counted under
        ``Serve/recovery.replay_sheds``), or ``"completed"`` (the crash
        landed between the final emit and the close record — the output
        was already fully delivered)."""
        if not tokens:
            raise ValueError("cannot replay an empty prompt")
        if uid in self.running or uid in self.eng.seqs \
                or any(r.uid == uid for r in self.queue):
            raise ValueError(f"uid {uid} is already being served")
        now = self.clock() if now is None else now
        out = [int(t) for t in emitted_tokens]
        rate = (rate_sla if rate_sla is not None
                else self.policy.token_rate_sla)
        if len(out) >= max_new_tokens:
            # fully delivered before the crash; only the close record was
            # lost — re-journal the final state (admit carrying the full
            # prefix, so THIS incarnation's journal is self-contained)
            # plus the missing close, and the next recovery skips the uid
            self._count("completed")
            if self.journal is not None:
                self.journal.admit(uid, tokens, max_new_tokens,
                                   tenant=tenant, rate_sla=rate, out=out,
                                   replayed=True)
                self.journal.close_request(uid, "done")
            self._trace("serve/admit", now, {
                "uid": int(uid), "n_tokens": len(tokens),
                "max_new_tokens": int(max_new_tokens), "tenant": tenant,
                "replayed": True, "watermark": len(out)})
            self._trace("serve/close", now,
                        {"uid": int(uid), "reason": "done"})
            return "completed"
        req = _Request(
            uid=uid, tokens=[int(t) for t in tokens],
            max_new_tokens=int(max_new_tokens), tenant=tenant,
            arrival_s=now, deadline_s=None, rate_sla=rate,
            budget=int(max_new_tokens) - len(out), out=out, queued_s=now)
        if out:
            # decode phase: slack scoring and the admission gate must see
            # the first token as delivered (see _activate's same rule for
            # requeued streams)
            req.first_token_s = now
        # replay gate: rate SLA only, and against the BEST-CASE (idle-
        # engine) measured rate — the replay set was running together
        # before the crash, so it is proven placeable; _gate's loaded-EWMA
        # heuristic would shed every replay after the first one re-fills
        # the engine. "Provably unmeetable" here means even an idle engine
        # cannot deliver the rate.
        # a replayed context is prime prefix-cache material: the donor
        # incarnation's committed blocks (or a sibling stream's) make the
        # re-prefill a block-table copy up to the first uncached token
        decision = "admit" if uid in self.eng.check_schedule(
            [uid], [req.n_prefill],
            cached_prefix={uid: self._peek_prefix(req)}).admitted \
            else "queue"
        if self.policy.admission != "none" and req.rate_sla > 0 \
                and self.capacity.decode_tok_s_best \
                < self.policy.rate_feasibility_margin * req.rate_sla:
            decision = "shed"
        if decision == "shed":
            self._count("shed")
            self._count_recovery("replay_sheds")
            if self.journal is not None:
                self.journal.close_request(uid, "replay_shed")
            self._trace("serve/close", now,
                        {"uid": int(uid), "reason": "replay_shed"})
            return "shed"
        if self.journal is not None:
            self.journal.admit(uid, req.tokens, req.max_new_tokens,
                               tenant=tenant, rate_sla=rate, out=out,
                               replayed=True)
        self._trace("serve/admit", now, {
            "uid": int(uid), "n_tokens": len(req.tokens),
            "max_new_tokens": req.max_new_tokens, "tenant": tenant,
            "rate_sla": rate, "replayed": True, "watermark": len(out)})
        # replay-segment edge: the survivor side of a failover-spanning
        # trace (generation/incarnation carried by the journal filename)
        self._stage(uid, "replay", now, watermark=len(out))
        self._count_recovery("replays")
        if decision == "admit" and not self.queue:
            self._activate(req, now)
        else:
            self.queue.append(req)
            self._count("queued")
        return "replayed"

    def _peek_prefix(self, req: _Request) -> int:
        """Cached-prefix length for ``req``'s full context, side-effect
        free (no counters, no recency) — the gate prices prefill at the
        NOVEL tokens only; the request may still be shed."""
        pc = self.eng.prefix_cache
        if pc is None:
            return 0
        return pc.peek(req.tokens + req.out, req.tenant)

    def _gate(self, req: _Request, now: float, ahead_tokens: int = 0) -> str:
        """admit | queue | shed for one request against the capacity model
        and the engine's structural limits. Prefill cost — both the KV
        block demand and the TTFT projection — is priced at
        ``n_prefill − cached_prefix_len``: a cached prefix is a
        block-table copy, not a forward."""
        cached = self._peek_prefix(req)
        res = self.eng.check_schedule([req.uid], [req.n_prefill],
                                      cached_prefix={req.uid: cached})
        structural_ok = req.uid in res.admitted
        if self.policy.admission == "none":
            return "admit" if structural_ok else "queue"
        # an IDLE engine projects at the best-case (least-loaded) measured
        # rates: the EWMA folds queueing delay in (the backpressure signal
        # while streams run), so after a shed-heavy phase empties the
        # engine it over-states service time — and with nothing admitted
        # no new samples would ever correct it (shed-everything lock-in)
        idle = not self.running
        # rate feasibility: a per-stream decode rate the hardware CLEARLY
        # cannot deliver is never meetable — admitting would only push the
        # already admitted streams' ITL over their SLA too. Margin < 1, not
        # headroom > 1: the EWMA breathes under load, and shedding the
        # whole fleet over a few-percent reading is the opposite of
        # graceful (TTFT projection is the overload valve)
        decode_rate = (self.capacity.decode_tok_s_best if idle
                       else self.capacity.decode_tok_s)
        if req.rate_sla > 0 and decode_rate \
                < self.policy.rate_feasibility_margin * req.rate_sla:
            return "shed"
        # TTFT projection only gates requests that have not started: a
        # requeued (evicted mid-decode) stream already delivered its first
        # token — its TTFT deadline is long past and meaningless; what it
        # must still sustain is the rate SLA, checked above
        if req.deadline_s is not None and req.first_token_s is None:
            slot_wait = 0.0 if structural_ok else self._slot_wait_s()
            eta = self.policy.sla_headroom * self.capacity.prefill_eta_s(
                self._prefill_backlog_tokens() + ahead_tokens
                + req.n_prefill - cached, best=idle)
            if now + slot_wait + eta > req.deadline_s:
                return "shed"
        if not structural_ok:
            return "queue" if self.policy.shed_policy == "queue" else "shed"
        return "admit"

    def _activate(self, req: _Request, now: float) -> None:
        """Hand the admitted request to the engine: descriptor created with
        its SLA budget BEFORE the first scheduler pass, prompt enqueued —
        the actual forwards run inside :meth:`step`."""
        d = self.eng.ensure_seq(
            req.uid, arrival_s=req.arrival_s, deadline_s=req.deadline_s,
            rate_sla=req.rate_sla, tenant=req.tenant,
            target_new_tokens=req.max_new_tokens, emitted=len(req.out),
            # a requeued stream keeps its first-token stamp: without it
            # slack_of scores the re-prefill against the long-expired TTFT
            # deadline (hugely negative slack) and the slack eviction
            # policies re-victimize the very stream we chose to resume
            first_token_s=req.first_token_s)
        # probe the prefix cache with the FULL context (prompt + emitted
        # prefix): an admission, a requeue after eviction and a crash
        # replay all re-enter here, so all three skip straight to the
        # first uncached token when the blocks are still indexed
        ctx = [int(t) for t in req.tokens] + [int(t) for t in req.out]
        cached = self.eng.map_cached_prefix(req.uid, ctx)
        d.pending.extend(ctx[cached:])
        d.last_logits = None
        req.enqueue_s = now
        req.cached_prefix_len = cached
        # queue-wait edge (admission→prefill dispatch; the prompt fuses
        # into the very next forward). A preemption-requeue re-enters here
        # as requeue_wait so the waterfall separates first-admission queue
        # time from re-admission backoff; both waits land in the satellite
        # Serve/queue_wait_s histogram.
        wait = max(0.0, now - req.queued_s)
        self._observe("Serve/queue_wait_s", wait)
        self._stage(req.uid,
                    "requeue_wait" if req.preempted else "queue_wait",
                    now, dur=wait, cached_prefix_len=cached,
                    novel_tokens=len(ctx) - cached)
        req.preempted = False
        self.running[req.uid] = req
        self._count("admitted")

    # --------------------------------------------------------- projections
    def _prefill_backlog_tokens(self) -> int:
        return sum(len(d.pending) for d in self.eng.seqs.values())

    def _queued_tokens(self) -> int:
        return sum(r.n_prefill for r in self.queue)

    def _slot_wait_s(self) -> float:
        """Earliest a slot/KV frees: the closest-to-done running stream's
        remaining tokens at the measured step time (∞ when nothing runs —
        structurally stuck)."""
        if not self.running:
            return math.inf
        rem = min(r.budget for r in self.running.values())
        return rem * self.capacity.decode_step_s

    def _slack_policy(self, now: float) -> SlackPolicy:
        return SlackPolicy(
            now=now, prefill_tok_s=self.capacity.prefill_tok_s,
            decode_tok_s=self.capacity.decode_tok_s,
            aging_weight=self.policy.aging_weight,
            tenant_budget=self.policy.tenant_token_budget)

    # -------------------------------------------------------------- stepping
    def step(self, now: Optional[float] = None) -> List[ServeEvent]:
        """One scheduling round; returns the round's event stream (possibly
        empty — e.g. nothing live and nothing admissible).

        The round's device dispatches run inside an armed stuck-decode
        watchdog window (``policy.watchdog_enabled``): a dispatch that
        never returns becomes a faulthandler dump + journal flush +
        ``os._exit(219)`` — the serving twin of the rc-218 collective-hang
        contract — instead of a silent forever-hang the supervisor can
        only guess at."""
        t_start = self.clock()
        now = t_start if now is None else now
        self._round += 1
        injector = get_fault_injector()
        rc = injector.should_serve_crash(self._round, self._tokens_emitted)
        if rc is not None:
            # a hard crash by definition: no journal close, no flush — the
            # per-record journal durability is what recovery rides
            logger.error("fault injection: serving process crashing "
                         "mid-decode (round %d, %d tokens emitted, rc=%d)",
                         self._round, self._tokens_emitted, rc)
            os._exit(rc)
        events: List[ServeEvent] = []
        # a round is timed and recorded only if it begins with work: a loop
        # that polls an idle session every millisecond must not push the
        # requests' own records out of the ring, nor grow the journal
        spans = self._spans = self._round_spans \
            if (self.running or self.queue) else None
        with (NO_PHASE if spans is None
              else spans.round(self._round, t_start)):
            wd = None
            try:
                with self._phase("queue"):
                    self._maintain_queue(now, events)
                    self.eng.slack_policy = self._slack_policy(now)
                    # arm only when the round has work: an idle poll (the
                    # natural serving-loop pattern while awaiting the first
                    # request) must not consume the one-shot warmup
                    # allowance — the first REAL round compiles prefill +
                    # sampler and needs it
                    wd = self.watchdog if (self.running or self.queue) \
                        else None
                    if wd is not None:
                        wd.arm(self._round)
                # the engine splits its own part of the round (put():
                # schedule, build, dispatch, collect) on the same clock
                self.eng.round_spans = spans
                dispatches0 = self.eng.host_dispatches
                # decode_wedge lands HERE — after arming, inside the watched
                # window — so the injected stall is exactly the hang the
                # watchdog exists to convert into rc 219
                injector.maybe_wedge_decode(self._round)
                self._per_token_round(now, events)
            finally:
                # disarm in a finally: an exception mid-round must not leave
                # the deadline live to rc-219 the process during ordinary
                # error handling (the PR 6 watchdog lesson)
                if wd is not None:
                    wd.disarm(self._round)
                self.eng.slack_policy = None
                self.eng.round_spans = None
            with self._phase("account"):
                self._note_progress(events, dispatches0, now)
                self._flush_gauges(now)
        if spans is not None:
            self._stamp_round(spans)
        return events

    def _note_progress(self, events: List[ServeEvent], dispatches0: int,
                       now: float) -> None:
        """Structured backpressure valve: a round with live streams that
        neither emitted an event nor dispatched anything is a wedged batch
        (KV pool exhausted with the remaining holders un-evictable, an
        injected ``kv_alloc_fail`` streak, allocator drift). After
        ``stall_patience_rounds`` such rounds the lowest-slack stream is
        preempted — requeued or rejected-with-partial-output per
        ``preempt_policy`` — so the batch un-wedges through the session's
        own event stream instead of an exception (or a caller's stall
        guard) killing the serving loop."""
        if events or self.eng.host_dispatches != dispatches0 \
                or not self.running:
            self._stall_rounds = 0
            return
        self._stall_rounds += 1
        if self._stall_rounds < self.policy.stall_patience_rounds:
            return
        self._stall_rounds = 0
        victim = self._eviction_victim(now)
        if victim is None:
            # no block-holding stream: fall back to lowest slack outright
            # (its re-prefill is the cheapest to redo)
            victim = min(self.running, key=lambda u: (
                slack_of(self.eng.seqs[u], now, self.capacity.prefill_tok_s,
                         self.capacity.decode_tok_s)
                if u in self.eng.seqs else 0.0))
        logger.warning("serving session: %d no-progress rounds with %d "
                       "live stream(s) — preempting uid %d to un-wedge "
                       "the batch", self.policy.stall_patience_rounds,
                       len(self.running), victim)
        self._evict(victim, now, events)

    def _maintain_queue(self, now: float, events: List[ServeEvent]) -> None:
        """Shed queued requests that aged out or became unmeetable; admit
        (in slack order) the ones the gate now accepts."""
        if not self.queue:
            return
        self.queue.sort(key=lambda r: (r.deadline_s is None,
                                       r.deadline_s or 0.0, r.arrival_s))
        kept: List[_Request] = []
        ahead = 0
        for req in self.queue:
            if now - req.queued_s > self.policy.max_queue_s:
                self._drop_queued(req, now, events, "queue timeout")
                continue
            decision = self._gate(req, now, ahead_tokens=ahead)
            if decision == "admit":
                self._activate(req, now)
            elif decision == "shed" and self.policy.admission != "none":
                self._drop_queued(req, now, events, "deadline unmeetable")
            else:
                kept.append(req)
                ahead += req.n_prefill
        self.queue = kept

    def _drop_queued(self, req: _Request, now: float,
                     events: List[ServeEvent], reason: str) -> None:
        """Terminal shed of a queued request. A requeued stream that
        already delivered tokens gets a ``finish`` (reason ``evicted``,
        partial output) instead of a bare ``shed`` — callers tracking
        completion must see closure for a request they received tokens
        from (one terminal event either way, never both)."""
        self._count("shed")
        self._slo_gate.append((now, True))
        close_reason = ("evicted" if req.first_token_s is not None
                        else f"shed:{reason}")
        if self.journal is not None:
            self.journal.close_request(req.uid, close_reason)
        self._trace("serve/close", now,
                    {"uid": int(req.uid), "reason": close_reason})
        if req.first_token_s is not None:
            events.append(ServeEvent("finish", req.uid, now,
                                     reason="evicted"))
        else:
            events.append(ServeEvent("shed", req.uid, now, reason=reason))

    # ------------------------------------------------------ per-token round
    def _per_token_round(self, now: float, events: List[ServeEvent]) -> None:
        """Sample over the last forward's logits, launch the NEXT forward,
        and only then read the sampled tokens back: the device goes from
        the sampler straight into the forward while the host waits for the
        copy, emits, and closes what ended. Between the sampler's launch and
        its read-back the token VALUES are the device's
        (``engine_v2.SampledTokens``): the forward takes them by reference,
        and what the round decides before the read-back (who decodes on, who
        is evicted, which prompt chunk rides along) rests on what the host
        knew before the last forward ended. A budget or the context's end is
        known; an EOS is not, and is learnt one forward late."""
        eng = self.eng
        # 1. one device sample over every drained stream, from the last
        # forward's whole logits: one launch at any count, nothing read yet
        with self._phase("gather"):
            drained = [uid for uid in self.running
                       if uid not in self._pending_tok
                       and eng.has_logits(uid)]
            if drained:
                self._rng, sub = split_key(self._rng)
        sampled = None
        ending: Dict[int, str] = {}
        reqs = {uid: self.running[uid] for uid in drained}
        if drained:
            # a sparse-expert model's last forward counted the experts it
            # touched on the device (and, holding a share of them, the rows
            # it gave them; a looped stack's, the rows by their exit pass):
            # the counts ride behind the tokens, and are read from the pool
            # the NEXT dispatch replaces, so the sampler goes first. The
            # engine times its own gather and sample
            sampled = eng.sample_launch(drained, sub, self.sampling,
                                        tail=eng.round_tail())
            with self._phase("schedule"):
                ending = self._plan_drained(reqs, sampled, now)
        else:
            self._last_decode_s = None  # no decode this round: break the
            #                             ITL chain across prefill-only gaps
        # 2. KV pressure: preempt the lowest-slack stream until the decode
        # tokens fit (never stall the whole batch on an exhausted pool).
        # Said after the tokens are: a victim's last token comes first
        evicted: List[_Request] = []
        put_uids = list(self._pending_tok)
        with self._phase("schedule"):
            while put_uids:
                res = eng.check_schedule(put_uids, [1] * len(put_uids))
                if not any(res.reasons.get(u, "").startswith("kv")
                           for u in res.rejected):
                    break
                victim = self._eviction_victim(now)
                if victim is None:
                    break
                evicted.append(self._preempt(victim))
                put_uids = [u for u in put_uids if u != victim]
            submit = bool(put_uids) or any(
                d.pending for d in eng.seqs.values())
        # 3. submit: decode tokens (by reference where the device still has
        # them) + (slack-ordered, tenant-capped) prompt chunks fuse into the
        # same forward inside put(), which splits its own time into
        # schedule, build, dispatch and collect
        if submit:
            self._submit(put_uids, sampled, reqs)
        # 4. the tokens: the host blocks HERE, behind a forward that runs
        if sampled is not None:
            self._emit_sampled(sampled, reqs, ending, evicted, events)
        if evicted:
            with self._phase("emit"):
                for req in evicted:
                    self._say_evicted(req, now, events)

    def _plan_drained(self, reqs: Dict[int, _Request], sampled,
                      now: float) -> Dict[int, str]:
        """What the host knows of each sampled stream before it has the
        token: one token more is out, and a stream whose budget or context
        that token ends closes with it. Those are released now and get no
        row in the next forward (``{uid: reason}``; said at the read-back);
        every other stream's token is pending by reference."""
        eng = self.eng
        ending: Dict[int, str] = {}
        for uid, req in reqs.items():
            d = eng.seqs[uid]
            req.budget -= 1
            d.emitted += 1
            if d.first_token_s is None:
                d.first_token_s = now  # for this round's slack order; the
                #                        read-back stamps the true instant
            if req.budget <= 0:
                ending[uid] = "done"
            elif d.n_cached >= eng.config.max_context:
                ending[uid] = "context"
            else:
                self._pending_tok[uid] = sampled.ref(uid)
                continue
            eng.flush([uid])
        return ending

    def _submit(self, put_uids: List[int], sampled,
                reqs: Dict[int, _Request]) -> None:
        """Launch the round's forward over ``put_uids``' decode tokens and
        whatever prompt chunks the scheduler adds."""
        eng = self.eng
        pend0 = ({u: len(d.pending) for u, d in eng.seqs.items()
                  if d.pending} if self._tracing else {})
        dispatches0 = eng.host_dispatches
        res = eng.put(put_uids, [[self._pending_tok[u]] for u in put_uids],
                      drain=False, sampled=sampled)
        with self._phase("account"):
            for uid in res.admission.admitted:
                self._pending_tok.pop(uid, None)
            if sampled is not None and eng.host_dispatches > dispatches0:
                # a forward is under way and the last one's tokens are unread
                self._launched_ahead += 1
                if self._spans is not None:
                    self._spans.fields["ahead"] = 1
            t1 = self.clock()
            # prefill-chunk edges: which uids advanced their prompt this
            # forward and by how many tokens. No duration: put() returns
            # when the forward is LAUNCHED, and the round record these
            # point to holds the launch and the round's true ends.
            for u, n0 in pend0.items():
                d = eng.seqs.get(u)
                n1 = len(d.pending) if d is not None else 0
                if n1 < n0:
                    self._stage(u, "prefill_chunk", t1, tokens=n0 - n1,
                                round=self._round)
            # first-token landings this pass: prefill capacity samples.
            # DELIBERATELY enqueue-to-first-token per request, not raw
            # forward throughput: the sample folds in the scheduling delay
            # a prompt experiences at the CURRENT concurrency, so the rate
            # sinks as load rises and the admission gate tightens — the
            # closed-loop backpressure that keeps admitted streams inside
            # their SLA under overload. A per-forward throughput sample
            # (budget tokens / forward time) reads ~constant regardless of
            # how many streams share the budget; gating on it admits far
            # past capacity and every admitted stream goes borderline-miss
            # (measured: 25-client shed 80%→28%, goodput 76→9 tok/s).
            # (a uid sampled this round is about to get its first token at
            # the read-back, so only freshly-landed prefills sample here)
            for uid, req in self.running.items():
                if req.first_token_s is None and uid not in reqs \
                        and eng.has_logits(uid):
                    self.capacity.record_prefill(len(req.tokens),
                                                 t1 - req.enqueue_s)

    def _emit_sampled(self, sampled, reqs: Dict[int, _Request],
                      ending: Dict[int, str], evicted: List[_Request],
                      events: List[ServeEvent]) -> None:
        """Read the sampled tokens back, hand them out and close what
        ended. A stream that ended on an EOS has, by now, a row in the
        forward that runs: a speculative row, whose logits nobody samples
        and whose KV lands in blocks released here (programs run in order
        on the device, and a block's next owner writes a position before it
        reads it). If it was preempted meanwhile (``evicted``) it is closed,
        not requeued."""
        eng = self.eng
        # the engine times the readback and gives the values to whatever
        # reference no forward ate
        toks, counted = eng.read_sampled(sampled)
        if counted is not None and self._spans is not None:
            self._spans.fields.update(eng.tail_fields(counted))
        t1 = self.clock()
        if self._last_decode_s is not None:
            self.capacity.record_decode(1, t1 - self._last_decode_s)
        self._last_decode_s = t1
        if self._spans is not None:
            self._spans.fields["uids"] = sorted(reqs)
        spec_rows = 0
        with self._phase("emit"):
            for (uid, req), tok in zip(reqs.items(), toks):
                tok = int(tok)
                events.append(ServeEvent("token", uid, t1, tokens=[tok]))
                self._note_emission(req, [tok], t1)
                eos = self.eos_token_id is not None \
                    and tok == self.eos_token_id
                spec_rows += eos and uid in sampled.taken
                if eos and req in evicted:
                    evicted.remove(req)
                    self.running[uid] = req
                if eos or uid in ending:
                    self._finish(uid, t1, events,
                                 "eos" if eos else ending[uid])
                elif uid in self._pending_tok:
                    self._pending_tok[uid] = tok   # put was refused: the
                    #                                value, for the next
        if spec_rows:
            self._spec_rows += spec_rows
            if self._spans is not None:
                self._spans.fields["spec_rows"] = spec_rows

    def _exclusive_blocks(self, uid: int) -> int:
        """Blocks only ``uid`` holds (refcount 1): preempting it frees
        exactly these — shared blocks stay alive under their other holders
        (sibling streams or the prefix index), so they buy no relief."""
        alloc = self.eng.allocator
        blocks = self.eng.seqs[uid].blocks
        if not hasattr(alloc, "refcount"):
            return len(blocks)
        return sum(1 for b in blocks if alloc.refcount(b) == 1)

    def _eviction_victim(self, now: float) -> Optional[int]:
        """Lowest slack first — the stream most likely to miss its SLA
        anyway; ties (e.g. every stream slack-less) break toward the most
        EXCLUSIVE (unshared) blocks, which buy the most actual relief —
        a stream riding a hot shared prefix frees almost nothing — then
        toward the longest context."""
        live = [u for u in self.running if u in self.eng.seqs
                and self.eng.seqs[u].blocks]
        if not live:
            return None
        return min(live, key=lambda u: (
            slack_of(self.eng.seqs[u], now, self.capacity.prefill_tok_s,
                     self.capacity.decode_tok_s),
            -self._exclusive_blocks(u),
            -self.eng.seqs[u].n_cached))

    def _evict(self, uid: int, now: float, events: List[ServeEvent]) -> None:
        self._say_evicted(self._preempt(uid), now, events)

    def _preempt(self, uid: int) -> _Request:
        """Take ``uid`` off the engine (blocks and slot freed) and out of
        the running set; :meth:`_say_evicted` tells the world."""
        req = self.running.pop(uid)
        self._pending_tok.pop(uid, None)
        self.eng.preempt(uid)
        return req

    def _say_evicted(self, req: _Request, now: float,
                     events: List[ServeEvent]) -> None:
        uid = req.uid
        self._count("evicted")
        requeue = self.policy.preempt_policy == "requeue"
        self._stage(uid, "preempt", now,
                    policy="requeue" if requeue else "reject")
        events.append(ServeEvent("evict", uid, now,
                                 reason="requeue" if requeue else "reject"))
        if requeue:
            # the emitted prefix is part of the context now — a fresh
            # prefill (tokens + out, rebuilt at activation) must restore
            # its KV before decode can continue. Still in flight: no
            # journal close (a crash here replays it from the watermark)
            req.queued_s = now
            req.preempted = True
            self.queue.append(req)
            self._count("queued")
        else:
            if self.journal is not None:
                self.journal.close_request(uid, "evicted")
            self._observe_stage_times(req)
            self._trace("serve/close", now,
                        {"uid": int(uid), "reason": "evicted"})
            events.append(ServeEvent("finish", uid, now, reason="evicted"))

    # ------------------------------------------------------------- plumbing
    def _note_emission(self, req: _Request, toks: Sequence[int],
                       t: float) -> None:
        req.out.extend(int(t_) for t_ in toks)
        self._tokens_emitted += len(toks)
        if self.journal is not None:
            # journal-before-release: the watermark is on disk before the
            # caller sees the tokens (step() returns the events after this),
            # which is what makes crash replay exactly-once
            self.journal.emit(req.uid, toks, len(req.out))
        self._trace("serve/emit", t, {"uid": int(req.uid), "n": len(toks),
                                      "round": self._round})
        if req.first_token_s is None:
            req.first_token_s = t
            d = self.eng.seqs.get(req.uid)
            if d is not None:
                d.first_token_s = t
            self._observe("Serve/ttft_s", t - req.arrival_s)
            # prefill edge closes at the first token; cached_prefix_len
            # makes the prefix-cache saving visible per request
            self._stage(req.uid, "prefill", t,
                        dur=max(0.0, t - req.enqueue_s),
                        cached_prefix_len=req.cached_prefix_len)
            if req.deadline_s is not None:
                self._slo_ttft.append((t, t <= req.deadline_s))
        elif req.last_emit_s is not None and toks:
            itl = (t - req.last_emit_s) / len(toks)
            for _ in toks:
                self._observe("Serve/itl_s", itl)
        req.last_emit_s = t

    def _finish(self, uid: int, now: float, events: List[ServeEvent],
                reason: str) -> None:
        req = self.running.pop(uid, None)
        self._pending_tok.pop(uid, None)
        self.eng.flush([uid])
        self._count("completed")
        if self.journal is not None:
            self.journal.close_request(uid, reason)
        if req is not None:
            self._observe_stage_times(req)
        self._trace("serve/close", now, {"uid": int(uid), "reason": reason})
        events.append(ServeEvent("finish", uid, now, reason=reason))

    def _observe_stage_times(self, req: _Request) -> None:
        """Terminal per-request phase self-times into the Serve/stage.*_s
        histograms (the streaming twin of the offline join's stage sums;
        guarded against requeue reorderings where first_token predates the
        last activation)."""
        if req.first_token_s is not None \
                and req.first_token_s >= req.enqueue_s:
            self._observe("Serve/stage.prefill_s",
                          req.first_token_s - req.enqueue_s)
        if req.first_token_s is not None and req.last_emit_s is not None \
                and req.last_emit_s > req.first_token_s:
            self._observe("Serve/stage.decode_s",
                          req.last_emit_s - req.first_token_s)

    def _count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n
        if self._metrics is not None:
            self._metrics.counter(f"Serve/{name}").incr(n)

    def _count_recovery(self, name: str, n: int = 1) -> None:
        self.recovery_counters[name] = \
            self.recovery_counters.get(name, 0) + n
        if self._metrics is not None:
            self._metrics.counter(_RECOVERY_COUNTERS[name]).incr(n)

    def _observe(self, name: str, value: float) -> None:
        if self._metrics is not None:
            self._metrics.histogram(name).observe(value)

    def _kv_occupancy(self) -> float:
        return kv_pool_stats(self.eng.kv, self.eng.allocator)["occupancy"]

    def _flush_gauges(self, now: Optional[float] = None) -> None:
        if self._metrics is None:
            return
        self._metrics.gauge("Serve/queue_depth").set(len(self.queue))
        self._metrics.gauge("Serve/kv_occupancy").set(self._kv_occupancy())
        self._metrics.gauge("Serve/live_seqs").set(len(self.running))
        window = getattr(self.eng.allocator, "window", None)
        if window is not None:    # a stack of two attention kinds
            self._metrics.gauge("Serve/kv.window_occupancy").set(
                1.0 - window.free_blocks / window.num_blocks)
        if now is not None:
            miss, shed, burn = self._slo_snapshot(now)
            self._metrics.gauge("Serve/slo.ttft_miss_frac").set(miss)
            self._metrics.gauge("Serve/slo.shed_frac").set(shed)
            self._metrics.gauge("Serve/slo.burn_rate").set(burn)
        pc = self.eng.prefix_cache
        if pc is not None:
            # the cache keeps lifetime totals; registry counters take the
            # delta since the last flush (monotone either way)
            for key, metric in _PREFIX_COUNTERS.items():
                delta = pc.counters[key] - self._prefix_reported.get(key, 0)
                if delta:
                    self._metrics.counter(metric).incr(delta)
                    self._prefix_reported[key] = pc.counters[key]
            self._metrics.gauge("Serve/prefix.hit_ratio").set(pc.hit_ratio)
            self._metrics.gauge("Serve/prefix.pinned_blocks").set(
                pc.pinned_blocks)

    # ------------------------------------------------------------ reporting
    @property
    def idle(self) -> bool:
        return not self.running and not self.queue

    def prefix_stats(self) -> Optional[Dict[str, float]]:
        """Prefix-cache counters + hit ratio (None when no cache is
        installed) — what the fleet router joins with its placement-side
        ``Fleet/affinity_hits`` for REALIZED reuse."""
        pc = self.eng.prefix_cache
        return None if pc is None else pc.stats()

    def stats(self) -> Dict[str, float]:
        """Counters + instantaneous state, for the worker's result file and
        operators."""
        out = {**self.counters,
               **{f"recovery_{n}": v
                  for n, v in self.recovery_counters.items()},
               "queue_depth": len(self.queue),
               "live_seqs": len(self.running),
               "trace_dropped": self.trace_dropped,
               "launched_ahead": self._launched_ahead,
               "speculative_rows": self._spec_rows,
               "kv_occupancy": round(self._kv_occupancy(), 4),
               "prefill_tok_s_est": round(self.capacity.prefill_tok_s, 1),
               "decode_step_s_est": round(self.capacity.decode_step_s, 5)}
        ps = self.prefix_stats()
        if ps is not None:
            out.update({f"prefix_{k}": v for k, v in ps.items()})
        return out

    def summary_events(self, step: Optional[int] = None) -> List[Tuple]:
        """Scalar ``Serve/*`` events for a MonitorMaster print boundary —
        validated against the telemetry registry (strict mode safe).
        TTFT/ITL histograms surface their estimated p50/p95/p99 (bucket-
        interpolated, ``Histogram.quantile``) alongside the raw bucket
        counts the registry already holds — the scalar a dashboard or the
        pod report's skew table actually wants."""
        from ...monitor.telemetry import check_events

        from ...monitor.telemetry import resilience_counters

        ev = [(f"Serve/{n}", float(v), step)
              for n, v in self.counters.items()]
        ev += [(_RECOVERY_COUNTERS[n], float(v), step)
               for n, v in self.recovery_counters.items()]
        ev += [("Serve/recovery.serve_hang_aborts",
                float(resilience_counters.get("serve_hang_aborts")), step),
               ("Serve/queue_depth", float(len(self.queue)), step),
               ("Serve/live_seqs", float(len(self.running)), step),
               ("Serve/kv_occupancy", self._kv_occupancy(), step)]
        # getattr chain: skeleton sessions (offline renderers, report
        # tests) carry no engine at all
        pc = getattr(getattr(self, "eng", None), "prefix_cache", None)
        if pc is not None:
            ev += [(_PREFIX_COUNTERS[n], float(pc.counters[n]), step)
                   for n in _PREFIX_COUNTERS]
            ev += [("Serve/prefix.hit_ratio", float(pc.hit_ratio), step),
                   ("Serve/prefix.pinned_blocks",
                    float(pc.pinned_blocks), step)]
        if getattr(self, "_slo_ttft", None) is not None \
                and getattr(self, "clock", None) is not None:
            miss, shed, burn = self._slo_snapshot(self.clock())
            ev += [("Serve/slo.ttft_miss_frac", miss, step),
                   ("Serve/slo.shed_frac", shed, step),
                   ("Serve/slo.burn_rate", burn, step)]
        if self._metrics is not None:
            for name in SERVE_HISTOGRAMS:
                hist = self._metrics.histogram(name)
                if not hist.count:
                    continue
                for q, value in hist.quantiles().items():
                    if value is not None:
                        ev.append((f"{name}/{q}", float(value), step))
        return check_events(ev)
