"""SLA serving-policy layer tests (``inference/v2/serving.py`` + the slack
scheduler + engine preemption hooks).

The policy is host-side and clock-driven, so everything here runs on the CPU
sim with a synthetic clock and a synthetic capacity model: admission
accept/queue/shed decisions, slack-ordered chunk composition (starvation
aging included), KV-exhaustion eviction picking the lowest-slack sequence
and actually freeing its blocks, per-tenant fairness budgets, and the
``Serve/*`` telemetry registration (strict-events safe).
"""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (
    BlockedAllocator, CapacityModel, InferenceEngineV2, ServingPolicyConfig,
    ServingSession)
from deepspeedsyclsupport_tpu.inference.v2.ragged import (
    SequenceDescriptor)
from deepspeedsyclsupport_tpu.inference.v2.scheduler import (
    SLACK_CAP, SlackPolicy, schedule_chunks, slack_of)
from deepspeedsyclsupport_tpu.inference.v2.serving import (
    SERVE_EVENT_NAMES)
from deepspeedsyclsupport_tpu.models import build_model
from tests.unit.greedy import greedy


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


@pytest.fixture(scope="module")
def tiny():
    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


def _v2(model, params, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("max_tokens_per_batch", 16)
    kw.setdefault("max_sequences", 4)
    return InferenceEngineV2(model, params, **kw)


def _drain(sess, out=None, max_steps=400):
    """Drive a session to idle, collecting token/finish/shed/evict events."""
    events = []
    steps = 0
    while not sess.idle:
        evs = sess.step()
        events.extend(evs)
        if out is not None:
            for e in evs:
                if e.kind == "token":
                    out.setdefault(e.uid, []).extend(e.tokens)
        steps += 1
        assert steps < max_steps, "session did not converge"
    return events


# ---------------------------------------------------------- capacity model
class TestCapacityModel:
    def test_first_sample_replaces_prior(self):
        cap = CapacityModel(prefill_tok_s=1000.0, decode_step_s=0.05,
                            alpha=0.5)
        cap.record_prefill(100, 1.0)          # measured: 100 tok/s
        assert cap.prefill_tok_s == pytest.approx(100.0)
        cap.record_prefill(300, 1.0)          # EWMA from here on
        assert cap.prefill_tok_s == pytest.approx(200.0)
        cap.record_decode(4, 2.0)             # 0.5 s/step replaces prior
        assert cap.decode_step_s == pytest.approx(0.5)
        assert cap.decode_tok_s == pytest.approx(2.0)

    def test_garbage_samples_ignored(self):
        cap = CapacityModel(prefill_tok_s=123.0)
        cap.record_prefill(0, 1.0)
        cap.record_prefill(10, 0.0)
        cap.record_decode(0, 1.0)
        assert cap.prefill_tok_s == pytest.approx(123.0)
        assert cap.prefill_eta_s(246) == pytest.approx(2.0)


# ---------------------------------------------------------- slack ordering
class TestSlackOf:
    def test_prefill_phase_slack(self):
        d = SequenceDescriptor(uid=1, pending=list(range(100)),
                               deadline_s=110.0)
        # 100 tokens at 50 tok/s = 2s of service; 10s to deadline → 8s slack
        assert slack_of(d, 100.0, prefill_tok_s=50.0) == pytest.approx(8.0)

    def test_decode_phase_slack(self):
        d = SequenceDescriptor(uid=1, n_cached=10, rate_sla=5.0,
                               target_new_tokens=20, emitted=10,
                               first_token_s=100.0)
        d.first_token_s = 100.0
        # implied finish deadline 100 + 20/5 = 104; at t=101 with 10 tokens
        # left at 10 tok/s (1s of service) → slack = 3 - 1 = 2
        assert slack_of(d, 101.0, decode_tok_s=10.0) == pytest.approx(2.0)

    def test_no_sla_is_inf(self):
        d = SequenceDescriptor(uid=1, pending=[1, 2])
        assert slack_of(d, 0.0) == math.inf


class TestSlackScheduling:
    def _mk(self, uid, pending, **kw):
        d = SequenceDescriptor(uid=uid, pending=list(pending))
        for k, v in kw.items():
            setattr(d, k, v)
        return d

    def test_urgent_prompt_first(self):
        alloc = BlockedAllocator(64)
        relaxed = self._mk(1, range(8), deadline_s=150.0, arrival_s=100.0,
                           last_service_s=100.0)
        urgent = self._mk(2, range(8), deadline_s=104.0, arrival_s=100.0,
                          last_service_s=100.0)
        pol = SlackPolicy(now=100.0, prefill_tok_s=100.0, aging_weight=0.0)
        chunks = schedule_chunks([relaxed, urgent], alloc, max_tokens=8,
                                 max_sequences=8, block_size=8,
                                 max_context=64, policy=pol)
        assert chunks[0][0] is urgent  # slack order, not arrival order

    def test_aging_lifts_starved_best_effort(self):
        """A no-deadline prompt that kept losing races accrues priority
        (SLACK_CAP bounds the inf slack) and eventually outranks an SLA
        prompt with comfortable slack — the starvation proof."""
        alloc = BlockedAllocator(64)
        sla = self._mk(1, range(8), deadline_s=100.0 + SLACK_CAP / 2,
                       arrival_s=100.0, last_service_s=100.0)
        starved = self._mk(2, range(8), arrival_s=100.0 - SLACK_CAP,
                           last_service_s=100.0 - SLACK_CAP)
        pol = SlackPolicy(now=100.0, prefill_tok_s=1e9, aging_weight=2.0)
        chunks = schedule_chunks([sla, starved], alloc, max_tokens=8,
                                 max_sequences=8, block_size=8,
                                 max_context=64, policy=pol)
        # starved: clamp(inf)=CAP minus 2*CAP aging → -CAP; sla: CAP/2
        assert chunks[0][0] is starved
        # without aging the SLA prompt wins
        pol0 = SlackPolicy(now=100.0, prefill_tok_s=1e9, aging_weight=0.0)
        chunks = schedule_chunks([sla, starved], alloc, max_tokens=8,
                                 max_sequences=8, block_size=8,
                                 max_context=64, policy=pol0)
        assert chunks[0][0] is sla

    def test_decode_slots_slack_ordered_under_budget(self):
        """When the token budget cannot carry every decode, the most urgent
        decode ships first."""
        alloc = BlockedAllocator(64)
        relaxed = self._mk(1, [5], n_cached=8, rate_sla=1.0,
                           target_new_tokens=100, emitted=1,
                           first_token_s=100.0, last_service_s=100.0)
        urgent = self._mk(2, [6], n_cached=8, rate_sla=100.0,
                          target_new_tokens=100, emitted=1,
                          first_token_s=100.0, last_service_s=100.0)
        for d in (relaxed, urgent):
            d.blocks = alloc.allocate(1)
        pol = SlackPolicy(now=100.0, decode_tok_s=1000.0, aging_weight=0.0)
        chunks = schedule_chunks([relaxed, urgent], alloc, max_tokens=1,
                                 max_sequences=8, block_size=8,
                                 max_context=64, policy=pol)
        assert len(chunks) == 1 and chunks[0][0] is urgent

    def test_tenant_budget_caps_prefill_per_round(self):
        """Per-tenant prefill token budget per scheduling round: tenant A's
        chunks cap at the budget, tenant B still gets its share — one noisy
        tenant cannot monopolize the forward."""
        alloc = BlockedAllocator(64)
        a1 = self._mk(1, range(8), tenant="A", last_service_s=100.0)
        a2 = self._mk(2, range(8), tenant="A", last_service_s=100.0)
        b1 = self._mk(3, range(8), tenant="B", last_service_s=100.0)
        pol = SlackPolicy(now=100.0, tenant_budget=4, aging_weight=0.0)
        chunks = schedule_chunks([a1, a2, b1], alloc, max_tokens=32,
                                 max_sequences=8, block_size=8,
                                 max_context=64, policy=pol)
        per_tenant = {}
        for d, n in chunks:
            per_tenant[d.tenant] = per_tenant.get(d.tenant, 0) + n
        assert per_tenant["A"] == 4 and per_tenant["B"] == 4
        # dict budgets with "*" default
        pol = SlackPolicy(now=100.0, tenant_budget={"A": 2, "*": 6},
                          aging_weight=0.0)
        chunks = schedule_chunks([a1, a2, b1], alloc, max_tokens=32,
                                 max_sequences=8, block_size=8,
                                 max_context=64, policy=pol)
        per_tenant = {}
        for d, n in chunks:
            per_tenant[d.tenant] = per_tenant.get(d.tenant, 0) + n
        assert per_tenant["A"] == 2 and per_tenant["B"] == 6

    def test_no_policy_keeps_legacy_order(self):
        alloc = BlockedAllocator(64)
        fresh = self._mk(1, range(8))
        fresh.last_scheduled = 5
        starved = self._mk(2, range(8))
        starved.last_scheduled = 1
        chunks = schedule_chunks([fresh, starved], alloc, max_tokens=8,
                                 max_sequences=8, block_size=8,
                                 max_context=64)
        assert chunks[0][0] is starved


# --------------------------------------------------------------- admission
class TestAdmission:
    def _session(self, tiny, clock, capacity, policy=None, **eng_kw):
        model, params = tiny
        eng = _v2(model, params, **eng_kw)
        pol = policy or ServingPolicyConfig(ttft_sla_s=10.0)
        return ServingSession(eng, pol, clock=clock, capacity=capacity), eng

    def test_accept_when_capacity_suffices(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1000.0, decode_step_s=0.01)
        sess, _ = self._session(tiny, clock, cap)
        assert sess.submit(1, [1, 2, 3], 4) == "admitted"
        assert sess.counters["admitted"] == 1

    def test_shed_when_projected_ttft_blows_deadline(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1000.0)
        cap.record_prefill(10, 10.0)  # measured: 1 tok/s
        sess, _ = self._session(tiny, clock, cap)
        # 30-token prompt at 1 tok/s ≈ 30s > 10s TTFT SLA → shed, not queue
        assert sess.submit(1, list(range(1, 31)), 4) == "shed"
        assert sess.counters["shed"] == 1 and not sess.queue

    def test_shed_on_infeasible_rate_sla(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1000.0)
        cap.record_decode(1, 1.0)  # measured: 1 tok/s per stream
        sess, _ = self._session(
            tiny, clock, cap,
            policy=ServingPolicyConfig(ttft_sla_s=1000.0,
                                       token_rate_sla=10.0))
        assert sess.submit(1, [1, 2, 3], 4) == "shed"

    def test_borderline_rate_is_not_shed(self, tiny):
        """Within rate_feasibility_margin of the SLA the gate admits: EWMA
        noise must not shed a fleet that is delivering ~SLA (the overload
        valve is the TTFT projection, not this check)."""
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1000.0)
        cap.record_decode(1, 0.11)  # 9.1 tok/s vs SLA 10: borderline
        sess, _ = self._session(
            tiny, clock, cap,
            policy=ServingPolicyConfig(ttft_sla_s=1000.0,
                                       token_rate_sla=10.0))
        assert sess.submit(1, [1, 2, 3], 4) == "admitted"

    def test_queue_on_slots_then_admit_when_freed(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        sess, eng = self._session(tiny, clock, cap, max_sequences=2)
        assert sess.submit(1, [1, 2, 3], 2) == "admitted"
        assert sess.submit(2, [4, 5], 2) == "admitted"
        # both slots held → structural queue (deadline still meetable)
        assert sess.submit(3, [6, 7], 2) == "queued"
        assert len(sess.queue) == 1
        out = {}
        _drain(sess, out)
        # the queued request was admitted once a slot freed and completed
        assert sess.counters["completed"] == 3 and sess.counters["shed"] == 0
        assert len(out[3]) == 2
        assert eng.allocator.free_blocks == eng.config.num_blocks

    def test_queue_timeout_sheds(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        pol = ServingPolicyConfig(admission="none", max_queue_s=5.0)
        sess, _ = self._session(tiny, clock, cap, policy=pol,
                                max_sequences=2)
        sess.submit(1, [1, 2, 3], 200)
        sess.submit(2, [4, 5], 200)
        assert sess.submit(3, [6, 7], 2) == "queued"
        clock.advance(6.0)
        evs = sess.step()
        sheds = [e for e in evs if e.kind == "shed"]
        assert len(sheds) == 1 and sheds[0].uid == 3
        assert sheds[0].reason == "queue timeout"

    def test_idle_engine_recovers_from_loaded_estimates(self, tiny):
        """No shed-everything lock-in: after a loaded phase drags the EWMA
        down (e2e samples fold queueing in — the backpressure signal), an
        IDLE engine projects at the best-case measured rate and admits —
        otherwise nothing is ever admitted again and no sample can correct
        the estimate."""
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1000.0)
        cap.record_prefill(512, 0.5)   # solo calibration: 1024 tok/s
        for _ in range(12):
            cap.record_prefill(512, 60.0)  # overload phase: ~8.5 tok/s e2e
        assert cap.prefill_tok_s < 100          # loaded EWMA is pessimistic
        assert cap.prefill_tok_s_best >= 1000.0  # best-case survives
        sess, _ = self._session(tiny, clock, cap)  # ttft_sla_s=10
        # idle engine: 30-token prompt at best-case ≈ 0.03s → admitted,
        # NOT shed on the stale loaded estimate (30/8.5 ≈ 3.5s would still
        # pass here, but a 512-token prompt would not: check both)
        assert sess.submit(1, list(range(30)), 4) == "admitted"

    def test_admission_none_never_deadline_sheds(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1000.0)
        cap.record_prefill(10, 10.0)  # 1 tok/s — would shed under "sla"
        pol = ServingPolicyConfig(admission="none")
        sess, _ = self._session(tiny, clock, cap, policy=pol)
        assert sess.submit(1, list(range(1, 31)), 2) == "admitted"


# ---------------------------------------------------- eviction / preemption
class TestEviction:
    def test_engine_preempt_frees_blocks_and_keeps_budget(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        eng.put([7], [[1, 2, 3, 4, 5, 6, 7, 8, 9]])
        assert eng.allocator.free_blocks < eng.config.num_blocks
        d = eng.preempt(7)
        assert d is not None and d.blocks == [] and d.n_cached == 0
        assert eng.allocator.free_blocks == eng.config.num_blocks
        assert 7 not in eng.seqs
        assert eng.preempt(7) is None

    def test_victim_is_lowest_slack(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        model, params = tiny
        eng = _v2(model, params)
        sess = ServingSession(eng, ServingPolicyConfig(), clock=clock,
                              capacity=cap)
        # behind-schedule stream (low slack) vs comfortable stream
        sess.submit(1, [1, 2, 3], 8, rate_sla=100.0, ttft_sla_s=100.0)
        sess.submit(2, [4, 5, 6], 8, rate_sla=0.001, ttft_sla_s=100.0)
        sess.step()  # prefill runs: both streams now HOLD blocks — only a
        #              block-holding stream is evictable (freeing nothing
        #              relieves nothing)
        for u in (1, 2):
            d = eng.seqs[u]
            d.first_token_s = clock()   # decode phase
            d.emitted = 1
            d.pending.clear()
        clock.advance(1.0)
        assert sess._eviction_victim(clock()) == 1
        eng.flush([1, 2])

    def test_kv_exhaustion_evicts_and_completes(self, tiny):
        """Tiny pool: the session preempts the lowest-slack stream (its
        blocks actually return to the pool), the survivors finish, and
        every evicted request reports a partial-output finish."""
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        model, params = tiny
        eng = _v2(model, params, num_blocks=4, block_size=8, max_context=32)
        sess = ServingSession(eng, ServingPolicyConfig(), clock=clock,
                              capacity=cap)
        # 3 + 10 tokens crosses the block boundary MID-decode (the final
        # sampled token is never appended, so gen must exceed
        # block_size - prompt + 1 for a stream to ever need block 2):
        # all three want a 2nd block with one free — preemption territory
        for uid, p in [(1, [1, 2, 3]), (2, [4, 5, 6]), (3, [7, 8, 9])]:
            assert sess.submit(uid, p, 10) == "admitted"
        events = _drain(sess)
        evicts = [e for e in events if e.kind == "evict"]
        finishes = {e.uid: e.reason for e in events if e.kind == "finish"}
        assert evicts, "pool of 4 blocks must force preemption"
        assert all(finishes[e.uid] == "evicted" for e in evicts)
        assert sess.counters["evicted"] == len(evicts)
        assert eng.allocator.free_blocks == 4  # everything reclaimed
        # every request resolved: survivors to full length, victims with a
        # partial-output reject ("completed" counts natural completions
        # only — an evicted-rejected stream is an SLA loss, not a finish)
        assert sess.counters["completed"] >= 1
        assert sess.counters["completed"] + sess.counters["evicted"] == 3

    def test_requeued_stream_not_shed_on_expired_ttft(self, tiny):
        """A requeued (evicted mid-decode) stream already delivered its
        first token: re-gating it against the long-expired TTFT deadline
        would shed every requeued stream — only the rate SLA applies."""
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        model, params = tiny
        eng = _v2(model, params, num_blocks=4, block_size=8, max_context=32)
        pol = ServingPolicyConfig(preempt_policy="requeue", ttft_sla_s=2.0)
        sess = ServingSession(eng, pol, clock=clock, capacity=cap)
        out = {}
        for uid, p in [(1, [1, 2, 3]), (2, [4, 5, 6]), (3, [7, 8, 9])]:
            assert sess.submit(uid, p, 10) == "admitted"
        # every step() call advances the clock past the 2s TTFT SLA: by
        # the time the pool exhausts and a stream is requeued, its
        # deadline is long past — it must still resume and complete
        steps = 0
        while not sess.idle and steps < 400:
            clock.advance(1.0)
            for e in sess.step():
                if e.kind == "token":
                    out.setdefault(e.uid, []).extend(e.tokens)
            steps += 1
        assert sess.counters["evicted"] > 0
        assert sess.counters["completed"] == 3
        assert all(len(v) == 10 for v in out.values()), out

    def test_requeue_policy_resumes_after_preemption(self, tiny):
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        model, params = tiny
        eng = _v2(model, params, num_blocks=4, block_size=8, max_context=32)
        pol = ServingPolicyConfig(preempt_policy="requeue")
        sess = ServingSession(eng, pol, clock=clock, capacity=cap)
        out = {}
        # gen 10 crosses the block boundary mid-decode (see above): the
        # pool must exhaust while all three streams are live
        for uid, p in [(1, [1, 2, 3]), (2, [4, 5, 6]), (3, [7, 8, 9])]:
            assert sess.submit(uid, p, 10) == "admitted"
        events = _drain(sess, out)
        evicts = [e for e in events if e.kind == "evict"]
        assert evicts and all(e.reason == "requeue" for e in evicts)
        # a requeued request is NOT a failed request: every stream
        # eventually delivers its full budget
        assert sess.counters["completed"] == 3
        assert all(len(v) == 10 for v in out.values()), out
        assert eng.allocator.free_blocks == 4


# ------------------------------------------------------------- session e2e
class TestSessionEndToEnd:
    def test_greedy_parity_and_slack_eviction_policy(self, tiny):
        """Tokens served under the full policy layer (admission + slack
        ordering) are exactly the naive greedy tokens."""
        model, params = tiny
        eng = _v2(model, params, eviction_policy="slack")
        sess = ServingSession(eng, ServingPolicyConfig(ttft_sla_s=30.0))
        prompts = {1: [7, 3, 11], 2: [4, 100, 42, 8, 19], 3: [9, 9, 2]}
        for uid, p in prompts.items():
            assert sess.submit(uid, p, 6) == "admitted"
        out = {}
        _drain(sess, out)
        for uid, p in prompts.items():
            assert out[uid] == greedy(model, params, p, 6)
        assert eng.allocator.free_blocks == eng.config.num_blocks

    def test_overload_degrades_gracefully(self, tiny):
        """More offered load than the capacity model can place: some
        requests shed, but the admitted ones COMPLETE — the r05 failure
        mode (everyone admitted, everyone misses) is structurally gone."""
        clock = FakeClock()
        cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
        cap.record_prefill(8, 1.0)  # measured: 8 tok/s — slow prefill
        model, params = tiny
        eng = _v2(model, params, max_sequences=2)
        pol = ServingPolicyConfig(ttft_sla_s=2.0, sla_headroom=1.0)
        sess = ServingSession(eng, pol, clock=clock, capacity=cap)
        decisions = [sess.submit(100 + i, [1 + i, 2, 3, 4, 5, 6, 7, 8], 2)
                     for i in range(6)]
        assert decisions.count("shed") >= 2     # backlog projection sheds
        assert "admitted" in decisions
        _drain(sess)
        assert sess.counters["completed"] == decisions.count("admitted")
        assert eng.allocator.free_blocks == eng.config.num_blocks

    def test_tenant_budget_plumbs_to_scheduler(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        pol = ServingPolicyConfig(tenant_token_budget={"A": 4, "*": 8})
        sess = ServingSession(eng, pol)
        sp = sess._slack_policy(0.0)
        assert sp.budget_for("A") == 4 and sp.budget_for("B") == 8
        assert SlackPolicy(tenant_budget=None).budget_for("x") == math.inf

    def test_duplicate_and_invalid_submits_rejected(self, tiny):
        model, params = tiny
        eng = _v2(model, params, max_sequences=2)
        sess = ServingSession(eng, ServingPolicyConfig())
        sess.submit(1, [1, 2], 2)
        with pytest.raises(ValueError, match="already"):
            sess.submit(1, [3], 2)
        # a QUEUED uid is also already-being-served: double-queueing it
        # would concatenate both prompts onto one descriptor at admission
        sess.submit(2, [4, 5], 2)
        assert sess.submit(9, [6, 7], 2) == "queued"  # slots full
        with pytest.raises(ValueError, match="already"):
            sess.submit(9, [8], 2)
        with pytest.raises(ValueError, match="empty"):
            sess.submit(2, [], 2)
        with pytest.raises(ValueError, match="max_new_tokens"):
            sess.submit(3, [1], 0)
        _drain(sess)


# ---------------------------------------------------------------- telemetry
class TestServeTelemetry:
    def test_serve_events_registered_strict(self, monkeypatch):
        from deepspeedsyclsupport_tpu.monitor.telemetry import (EVENT_NAMES,
                                                                check_events)

        monkeypatch.setenv("DSTPU_STRICT_EVENTS", "1")
        assert set(SERVE_EVENT_NAMES) <= EVENT_NAMES
        # strict mode accepts every Serve/* name this layer emits
        check_events([(n, 1.0, 0) for n in SERVE_EVENT_NAMES])

    def test_recovery_family_registered_and_emitted(self, tiny, monkeypatch):
        """``Serve/recovery.*`` strict-registry family: counters and the
        time-to-recover histogram (p50/p95/p99 quantile events) are
        declared, fed by replay, and emitted by ``summary_events`` under
        strict mode."""
        from deepspeedsyclsupport_tpu.monitor.telemetry import (
            EVENT_NAMES, metrics_registry)

        monkeypatch.setenv("DSTPU_STRICT_EVENTS", "1")
        expected = {"Serve/recovery.replays", "Serve/recovery.replay_sheds",
                    "Serve/recovery.serve_hang_aborts",
                    "Serve/recovery.time_to_recover_s"}
        expected |= {f"Serve/recovery.time_to_recover_s/{q}"
                     for q in ("p50", "p95", "p99")}
        assert expected <= EVENT_NAMES
        model, params = tiny
        eng = _v2(model, params)
        sess = ServingSession(eng, ServingPolicyConfig())
        base = metrics_registry.counter("Serve/recovery.replays").value
        assert sess.replay(41, [7, 3, 11], 3) == "replayed"
        _drain(sess)
        assert metrics_registry.counter(
            "Serve/recovery.replays").value == base + 1
        metrics_registry.histogram(
            "Serve/recovery.time_to_recover_s").observe(1.5)
        ev = sess.summary_events(step=2)  # validates under strict mode
        names = {n for n, _v, _s in ev}
        assert {"Serve/recovery.replays", "Serve/recovery.replay_sheds",
                "Serve/recovery.serve_hang_aborts",
                "Serve/recovery.time_to_recover_s/p50"} <= names
        by_name = {n: v for n, v, _s in ev}
        assert by_name["Serve/recovery.replays"] >= 1.0

    def test_session_feeds_metrics_registry(self, tiny, monkeypatch):
        from deepspeedsyclsupport_tpu.monitor.telemetry import \
            metrics_registry

        monkeypatch.setenv("DSTPU_STRICT_EVENTS", "1")
        model, params = tiny
        eng = _v2(model, params)
        sess = ServingSession(eng, ServingPolicyConfig(ttft_sla_s=30.0))
        base = metrics_registry.counter("Serve/admitted").value
        sess.submit(1, [7, 3, 11], 3)
        _drain(sess)
        assert metrics_registry.counter("Serve/admitted").value == base + 1
        assert metrics_registry.histogram("Serve/ttft_s").count >= 1
        assert metrics_registry.gauge("Serve/kv_occupancy").value == 0.0
        # summary events validate against the registry under strict mode
        ev = sess.summary_events(step=1)
        assert ("Serve/completed", 1.0, 1) in [
            (n, v, s) for n, v, s in ev if n == "Serve/completed"]

    def test_config_validation(self):
        with pytest.raises(ValueError, match="admission"):
            ServingPolicyConfig(admission="maybe")
        with pytest.raises(ValueError, match="shed_policy"):
            ServingPolicyConfig(shed_policy="drop")
        with pytest.raises(ValueError, match="preempt_policy"):
            ServingPolicyConfig(preempt_policy="explode")
        with pytest.raises(ValueError, match="rate_feasibility_margin"):
            ServingPolicyConfig(rate_feasibility_margin=0.0)
        with pytest.raises(ValueError, match="unknown serving policy"):
            ServingPolicyConfig.from_config({"no_such_knob": 1})
        with pytest.raises(ValueError, match="eviction_policy"):
            InferenceEngineV2  # noqa: B018 — see engine config test below
            from deepspeedsyclsupport_tpu.inference.v2.config import \
                RaggedInferenceConfig
            RaggedInferenceConfig(eviction_policy="coinflip")
