"""Serving-plane fault tolerance suite (ISSUE 11).

Covers the tentpole pieces and their satellites:

* the request journal (``inference/v2/supervisor.RequestJournal``):
  admit/emit/close records flushed per line, cross-incarnation merge with
  torn-tail salvage, output reconstruction;
* crash-replay recovery (``ServingSession.replay`` +
  ``supervisor.recover_requests``): resume from the emitted-token
  watermark with zero duplicate/missing tokens, rate-SLA-only re-gating
  (TTFT is burned), terminal ``replay_shed`` accounting, the
  ``Serve/recovery.*`` strict-registry family;
* the stuck-decode watchdog: rc 219 (``SERVE_HANG_EXIT_CODE``) fire path
  with ``serve/arm``/``serve/hang`` records into the journal stream,
  ``serve_hang_aborts`` counting, the elastic agent / replica
  supervisor's per-cause rc-219 restart class;
* serving fault injection (``decode_wedge`` / ``serve_crash`` /
  ``kv_alloc_fail``) and the structured-backpressure contract: an
  injected (or real) KV allocation failure queues/sheds through the
  session — the engine loop never dies on an exception, and a wedged
  batch self-heals by preempting the lowest-slack stream;
* double-eviction and replay-then-eviction idempotency: the context
  rebuild (immutable prompt + emitted prefix) survives two consecutive
  preemptions of the same stream AND a journal replay followed by a
  preemption, with a dispatch spy asserting no token is ever re-emitted.

The real two-process chaos end-to-ends (supervisor + engine worker with an
injected mid-decode ``serve_crash`` / ``decode_wedge``) are ``slow``-marked
— each pays two engine compiles in subprocesses. ``TestCrashReplaySmoke``
is their tier-1-safe in-process twin (same journal, same replay path, no
subprocess/compile cost beyond the shared tiny model).
"""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from deepspeedsyclsupport_tpu.comm.watchdog import (
    COMM_HANG_EXIT_CODE, SERVE_HANG_EXIT_CODE, CollectiveWatchdog)
from deepspeedsyclsupport_tpu.elasticity import DSElasticAgent
from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ReplicaSupervisor, RequestJournal, ServingPolicyConfig,
    ServingSession, load_journal, reconstruct_outputs, recover_requests)
from deepspeedsyclsupport_tpu.monitor.monitor import (
    resilience_counters)
from deepspeedsyclsupport_tpu.utils.fault_injection import (
    ENV_SPEC, FaultInjector, configure_fault_injection)
from deepspeedsyclsupport_tpu.models import build_model

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv(ENV_SPEC, raising=False)
    monkeypatch.delenv("DSTPU_ELASTIC_ATTEMPT", raising=False)
    configure_fault_injection(None)
    resilience_counters.reset()
    yield
    configure_fault_injection(None)
    resilience_counters.reset()


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


PROMPTS = {1: [7, 3, 11], 2: [4, 100, 42, 8, 19], 3: [9, 9, 2]}


def _drive(sess, out=None, max_steps=500):
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


def _baseline(tiny, gen=6):
    model, params = tiny
    sess = ServingSession(_v2(model, params), ServingPolicyConfig())
    for uid, p in PROMPTS.items():
        assert sess.submit(uid, p, gen) == "admitted"
    out = {}
    _drive(sess, out)
    return out


# ============================================================== journal
class TestRequestJournal:
    def test_admit_emit_close_roundtrip(self, tmp_path):
        path = str(tmp_path / "journal_rank0.att0.jsonl")
        j = RequestJournal(path)
        j.admit(5, [1, 2, 3], 8, tenant="t", rate_sla=2.0, ttft_sla_s=1.5)
        j.emit(5, [42], 1)
        j.emit(5, [43, 44], 3)
        j.close_request(5, "done")
        j.close()
        states, last_t = load_journal(path)
        assert last_t > 0
        st = states[5]
        assert st.tokens == [1, 2, 3] and st.max_new_tokens == 8
        assert st.tenant == "t" and st.rate_sla == 2.0
        assert st.out == [42, 43, 44]
        assert st.closed and st.reason == "done"
        assert reconstruct_outputs(states) == {5: [42, 43, 44]}

    def test_every_record_is_flushed(self, tmp_path):
        """Per-record durability IS the replay contract: a token the
        client saw must be on disk the instant it is released — no
        buffered tail for a crash to eat."""
        path = str(tmp_path / "journal_rank0.att0.jsonl")
        j = RequestJournal(path)
        j.admit(1, [1], 4)
        j.emit(1, [9], 1)
        # no close(), no flush(): the file must already hold both records
        states, _ = load_journal(path)
        assert states[1].out == [9] and not states[1].closed
        j.close()

    def test_torn_tail_salvage(self, tmp_path):
        path = str(tmp_path / "journal_rank0.att0.jsonl")
        j = RequestJournal(path)
        j.admit(1, [1, 2], 6)
        j.emit(1, [7], 1)
        j.close()
        with open(path, "a") as f:
            f.write('{"kind": "event", "name": "serve/emit", "da')  # torn
        states, _ = load_journal(path)
        assert states[1].out == [7] and not states[1].closed

    def test_multi_incarnation_merge(self, tmp_path):
        """A replayed admit (incarnation 2) carries the watermark prefix;
        later emits continue it — reconstruction never duplicates."""
        p0 = str(tmp_path / "journal_rank0.att0.jsonl")
        p1 = str(tmp_path / "journal_rank0.att1.jsonl")
        j0 = RequestJournal(p0)
        j0.admit(1, [1, 2], 6)
        j0.emit(1, [10, 11], 2)
        j0.close()
        time.sleep(0.02)  # distinct mtime granule: att0 sorts first
        j1 = RequestJournal(p1)
        j1.admit(1, [1, 2], 6, out=[10, 11], replayed=True)
        j1.emit(1, [12], 3)
        j1.close_request(1, "done")
        j1.close()
        states, _ = load_journal(str(tmp_path))
        assert states[1].out == [10, 11, 12] and states[1].closed

    def test_session_journals_lifecycle(self, tiny, tmp_path):
        """Driving a journaled session end-to-end leaves every request
        closed with its full emit stream on disk."""
        model, params = tiny
        path = str(tmp_path / "journal_rank0.att0.jsonl")
        sess = ServingSession(_v2(model, params),
                              ServingPolicyConfig(journal_path=path))
        for uid, p in PROMPTS.items():
            assert sess.submit(uid, p, 4) == "admitted"
        out = {}
        _drive(sess, out)
        sess.close()
        states, _ = load_journal(path)
        assert set(states) == set(PROMPTS)
        for uid, st in states.items():
            assert st.closed and st.reason == "done"
            assert st.out == out[uid]
        assert reconstruct_outputs(states) == out


# =============================================================== replay
class TestReplay:
    def test_replay_resumes_from_watermark_no_duplicates(self, tiny):
        base = _baseline(tiny)
        model, params = tiny
        sess = ServingSession(_v2(model, params), ServingPolicyConfig())
        got = {}
        for uid in PROMPTS:
            # pretend incarnation 1 delivered a 2-token prefix
            assert sess.replay(uid, PROMPTS[uid], 6,
                               emitted_tokens=base[uid][:2]) == "replayed"
            got[uid] = list(base[uid][:2])
        _drive(sess, got)
        assert got == base  # continuation, not repetition
        assert sess.recovery_counters["replays"] == len(PROMPTS)

    def test_replay_regates_on_rate_only(self, tiny):
        """An expired-TTFT replay must NOT shed on the TTFT projection —
        only a provably-unmeetable rate SLA sheds it (PR 4's requeue
        rule, extended to journal replay)."""
        from deepspeedsyclsupport_tpu.inference.v2 import CapacityModel

        model, params = tiny
        cap = CapacityModel(prefill_tok_s=1000.0)
        cap.record_prefill(10, 10.0)   # 1 tok/s: any TTFT gate would shed
        cap.record_decode(1, 1.0)      # 1 tok/s decode
        sess = ServingSession(_v2(model, params),
                              ServingPolicyConfig(ttft_sla_s=0.001),
                              capacity=cap)
        # prefix delivered → TTFT burned → replayed despite the dead TTFT
        assert sess.replay(1, list(range(1, 31)), 6,
                           emitted_tokens=[5], rate_sla=0.5) == "replayed"
        # hardware-can-never-do-it rate → terminal replay shed
        assert sess.replay(2, [1, 2, 3], 6, emitted_tokens=[5],
                           rate_sla=100.0) == "shed"
        assert sess.recovery_counters == {"replays": 1, "replay_sheds": 1}

    def test_replay_of_fully_delivered_request_closes(self, tiny, tmp_path):
        """Crash between the final emit and the close record: replay
        recognizes the budget as spent, writes the missing close, and the
        NEXT recovery skips the uid entirely."""
        model, params = tiny
        path = str(tmp_path / "journal_rank0.att1.jsonl")
        sess = ServingSession(_v2(model, params),
                              ServingPolicyConfig(journal_path=path))
        assert sess.replay(1, [1, 2], 4,
                           emitted_tokens=[9, 8, 7, 6]) == "completed"
        assert sess.counters["completed"] == 1
        sess.close()
        states, _ = load_journal(path)
        assert states[1].closed and states[1].reason == "done"

    def test_recover_requests_summary_and_histogram(self, tiny, tmp_path):
        from deepspeedsyclsupport_tpu.monitor.telemetry import \
            metrics_registry

        model, params = tiny
        p0 = str(tmp_path / "journal_rank0.att0.jsonl")
        j0 = RequestJournal(p0)
        j0.admit(1, [7, 3, 11], 6)
        j0.emit(1, [42], 1)
        j0.admit(2, [9, 9, 2], 4)
        j0.close_request(2, "done")
        j0.close()
        states, last_t = load_journal(p0)
        sess = ServingSession(_v2(model, params), ServingPolicyConfig())
        hist = metrics_registry.histogram("Serve/recovery.time_to_recover_s")
        n0 = hist.count
        summary = recover_requests(sess, states, last_t)
        assert summary["replayed"] == [1]
        assert summary["skipped_closed"] == [2]
        assert summary["time_to_recover_s"] is not None
        assert hist.count == n0 + 1
        _drive(sess)


class TestCrashReplaySmoke:
    """Tier-1-safe in-process twin of the two-process chaos e2e: same
    journal, same replay path — the 'crash' abandons the session and
    engine KV state mid-decode without closing anything."""

    def test_inprocess_crash_replay_token_equality(self, tiny, tmp_path):
        base = _baseline(tiny)
        model, params = tiny
        p0 = str(tmp_path / "journal_rank0.att0.jsonl")
        eng = _v2(model, params)
        sess = ServingSession(eng, ServingPolicyConfig(journal_path=p0))
        for uid, p in PROMPTS.items():
            assert sess.submit(uid, p, 6) == "admitted"
        got = {}
        steps = 0
        while sum(len(v) for v in got.values()) < 7 and steps < 100:
            for e in sess.step():
                if e.kind == "token":
                    got.setdefault(e.uid, []).extend(e.tokens)
            steps += 1
        assert any(got.values()), "need a mid-decode crash point"
        # crash: no close, no flush — KV state and descriptors are lost
        del sess
        eng.flush(list(eng.seqs))

        p1 = str(tmp_path / "journal_rank0.att1.jsonl")
        states, last_t = load_journal(p0)
        assert all(not st.closed for st in states.values())
        sess2 = ServingSession(_v2(model, params),
                               ServingPolicyConfig(journal_path=p1))
        summary = recover_requests(sess2, states, last_t)
        assert sorted(summary["replayed"]) == sorted(PROMPTS)
        _drive(sess2, got)
        sess2.close()
        # zero duplicate, zero missing: byte-for-byte the uninterrupted run
        assert got == base
        # and the merged journal reconstructs the same delivery record
        final, _ = load_journal(str(tmp_path))
        assert reconstruct_outputs(final) == base
        assert all(st.closed for st in final.values())


# ===================================================== eviction idempotency
class TestEvictionIdempotency:
    def _spy_dispatch(self, eng, log):
        """Record every scheduled chunk's tokens at the DISPATCH seam
        (``engine._run`` — prompts reach the device through descriptor
        pending state, never through put()'s arguments)."""
        orig = eng._run

        def spy(chunks, *sampled):
            for d, n in chunks:
                log.append((d.uid, list(d.pending[:n])))
            return orig(chunks, *sampled)

        eng._run = spy
        return eng

    def test_two_consecutive_evictions_no_duplicate_tokens(self, tiny):
        """The PR 4 context-rebuild guarantee across TWO evictions of the
        same stream: each re-admission prefills exactly prompt + emitted
        prefix (dispatch spy), and the final output equals the
        uninterrupted run — no token ever re-emitted."""
        base = _baseline(tiny)
        model, params = tiny
        eng = _v2(model, params)
        dispatched = []
        self._spy_dispatch(eng, dispatched)
        sess = ServingSession(eng,
                              ServingPolicyConfig(preempt_policy="requeue"))
        uid = 2
        assert sess.submit(uid, PROMPTS[uid], 6) == "admitted"
        got = {}

        def evict_after(n_tokens):
            steps = 0
            while len(got.get(uid, [])) < n_tokens and steps < 100:
                for e in sess.step():
                    if e.kind == "token":
                        got.setdefault(e.uid, []).extend(e.tokens)
                steps += 1
            evs = []
            sess._evict(uid, sess.clock(), evs)
            assert evs[0].kind == "evict" and evs[0].reason == "requeue"

        evict_after(2)   # first eviction: 2 tokens out
        prefix1 = list(got[uid])
        evict_after(4)   # re-admitted, then evicted AGAIN mid-decode
        prefix2 = list(got[uid])
        assert prefix2[:len(prefix1)] == prefix1  # monotonic watermark
        _drive(sess, got)
        assert got[uid] == base[uid]
        # every re-prefill the engine saw is exactly prompt + prefix-then
        rebuilds = [t for u, t in dispatched
                    if u == uid and len(t) > 1]
        assert rebuilds[0] == PROMPTS[uid]
        assert rebuilds[1] == PROMPTS[uid] + prefix1
        assert rebuilds[2] == PROMPTS[uid] + prefix2

    def test_replay_then_eviction_idempotent(self, tiny):
        """Journal-replay extension: a replayed stream that is then
        evicted and requeued still rebuilds prompt + full prefix — the
        replayed prefix is immutable context, not re-emittable output."""
        base = _baseline(tiny)
        model, params = tiny
        eng = _v2(model, params)
        dispatched = []
        self._spy_dispatch(eng, dispatched)
        sess = ServingSession(eng,
                              ServingPolicyConfig(preempt_policy="requeue"))
        uid = 1
        prefix = base[uid][:3]
        assert sess.replay(uid, PROMPTS[uid], 6,
                           emitted_tokens=prefix) == "replayed"
        got = {uid: list(prefix)}
        steps = 0
        while len(got[uid]) < 4 and steps < 100:
            for e in sess.step():
                if e.kind == "token":
                    got[e.uid].extend(e.tokens)
            steps += 1
        evs = []
        sess._evict(uid, sess.clock(), evs)
        mid = list(got[uid])
        _drive(sess, got)
        assert got[uid] == base[uid]
        rebuilds = [t for u, t in dispatched if u == uid and len(t) > 1]
        assert rebuilds[0] == PROMPTS[uid] + prefix
        assert rebuilds[1] == PROMPTS[uid] + mid


# ==================================================== backpressure / faults
class TestKvBackpressure:
    def test_try_allocate_reports_injected_exhaustion(self):
        from deepspeedsyclsupport_tpu.inference.v2 import BlockedAllocator

        alloc = BlockedAllocator(4)
        configure_fault_injection({"kv_alloc_fail": {"count": 1}})
        assert alloc.try_allocate(2) is None      # injected failure
        assert alloc.free_blocks == 4             # nothing leaked
        got = alloc.try_allocate(2)               # one-shot: next succeeds
        assert got is not None and alloc.free_blocks == 2
        assert alloc.try_allocate(3) is None      # real exhaustion
        with pytest.raises(RuntimeError, match="exhausted"):
            alloc.allocate(3)                     # raising contract intact

    def test_injected_alloc_failures_never_kill_the_loop(self, tiny):
        """A streak of injected allocation failures degrades to retries /
        evictions through the session — every stream still completes its
        full budget and the pool is fully reclaimed."""
        model, params = tiny
        eng = _v2(model, params, num_blocks=4, block_size=8, max_context=32)
        sess = ServingSession(eng,
                              ServingPolicyConfig(preempt_policy="requeue"))
        for uid, p in PROMPTS.items():
            assert sess.submit(uid, p, 10) == "admitted"
        configure_fault_injection({"kv_alloc_fail": {"count": 6}})
        out = {}
        _drive(sess, out)
        assert {u: len(v) for u, v in out.items()} == \
            {u: 10 for u in PROMPTS}
        assert eng.allocator.free_blocks == 4

    def test_stalled_batch_self_heals_by_preemption(self, tiny):
        """The structured-backpressure valve: rounds that neither emit nor
        dispatch with live streams trigger a preemption after
        stall_patience_rounds — the session un-wedges itself instead of
        relying on a caller's stall guard."""
        model, params = tiny
        eng = _v2(model, params)
        pol = ServingPolicyConfig(preempt_policy="requeue",
                                  stall_patience_rounds=2)
        sess = ServingSession(eng, pol)
        assert sess.submit(1, [1, 2, 3], 4) == "admitted"
        # wedge the stream artificially: drained logits withheld and no
        # pending input — the engine can neither sample nor schedule it
        _drive_one = sess.step()  # prefill runs
        d = eng.seqs[1]
        d.last_logits = None
        d.pending.clear()
        sess._pending_tok.pop(1, None)
        evs1 = sess.step()
        assert not evs1  # first stalled round: patience
        evs2 = sess.step()
        evicts = [e for e in evs2 if e.kind == "evict"]
        assert len(evicts) == 1 and evicts[0].uid == 1
        assert sess.queue and sess.queue[0].uid == 1  # requeued, in flight
        out = {}
        _drive(sess, out)
        assert len(out[1]) == 4  # the requeued stream still completes


class TestServeFaultInjection:
    def test_serve_crash_gates(self):
        fi = FaultInjector({"serve_crash": {"tokens": 10, "rc": 3}})
        assert fi.should_serve_crash(1, 9) is None
        assert fi.should_serve_crash(2, 10) == 3
        assert fi.should_serve_crash(3, 99) is None  # one-shot
        fi = FaultInjector({"serve_crash": {"round": 5}})
        assert fi.should_serve_crash(4, 1000) is None
        assert fi.should_serve_crash(5, 0) == 1

    def test_attempt_gate(self, monkeypatch):
        spec = {"serve_crash": {"tokens": 1, "attempt": 1}}
        monkeypatch.setenv("DSTPU_ELASTIC_ATTEMPT", "0")
        assert FaultInjector(spec).should_serve_crash(1, 5) is None
        monkeypatch.setenv("DSTPU_ELASTIC_ATTEMPT", "1")
        assert FaultInjector(spec).should_serve_crash(1, 5) == 1

    def test_decode_wedge_blocks_in_window(self):
        fi = FaultInjector({"decode_wedge": {"round": 2, "seconds": 0.05}})
        assert not fi.maybe_wedge_decode(1)
        t0 = time.perf_counter()
        assert fi.maybe_wedge_decode(2)
        assert time.perf_counter() - t0 >= 0.05
        assert not fi.maybe_wedge_decode(3)  # one-shot


# ============================================================== watchdog
class TestServeWatchdog:
    def _wd(self, journal=None, **kw):
        kw.setdefault("deadline_s", 0.1)
        kw.setdefault("warmup_deadline_s", 0.1)
        kw.setdefault("poll_s", 0.02)
        fired = []
        wd = CollectiveWatchdog(telemetry=journal,
                                exit_fn=lambda rc: fired.append(rc),
                                exit_code=SERVE_HANG_EXIT_CODE,
                                abort_counter="serve_hang_aborts",
                                arm_name="serve/arm",
                                hang_name="serve/hang",
                                what="serving decode", **kw)
        return wd, fired

    def test_fires_rc219_and_counts_serve_hang(self, tmp_path):
        journal = RequestJournal(str(tmp_path / "journal_rank0.att0.jsonl"))
        wd, fired = self._wd(journal=journal)
        n0 = resilience_counters.get("serve_hang_aborts")
        wd.start()
        wd.arm(7)
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.02)
        wd.stop()
        journal.close()
        assert fired == [SERVE_HANG_EXIT_CODE]
        assert resilience_counters.get("serve_hang_aborts") == n0 + 1
        # arm + hang records landed in the journal stream, step-matched
        recs = [json.loads(ln) for ln in
                open(str(tmp_path / "journal_rank0.att0.jsonl"))]
        names = {r["name"]: r for r in recs}
        assert names["serve/arm"]["step"] == 7
        assert names["serve/hang"]["step"] == 7

    def test_disarm_prevents_fire(self):
        wd, fired = self._wd()
        wd.start()
        wd.arm(1)
        wd.disarm(1)
        time.sleep(0.3)
        wd.stop()
        assert not fired

    def test_session_arms_and_disarms_per_round(self, tiny, tmp_path):
        """The session's rounds run inside armed windows; a healthy drive
        never fires, and the arm records land in the journal."""
        model, params = tiny
        path = str(tmp_path / "journal_rank0.att0.jsonl")
        pol = ServingPolicyConfig(journal_path=path, watchdog_enabled=True,
                                  watchdog_deadline_s=60.0)
        sess = ServingSession(_v2(model, params), pol)
        assert sess.watchdog is not None
        assert sess.watchdog.exit_code == SERVE_HANG_EXIT_CODE
        sess.submit(1, [7, 3, 11], 3)
        _drive(sess)
        assert sess.watchdog._inflight is None  # disarmed between rounds
        sess.close()
        assert sess.watchdog._thread is None    # close() reaped the poller
        arms = [json.loads(ln) for ln in open(path)
                if '"serve/arm"' in ln]
        assert arms and all(r["data"]["deadline_s"] > 0 for r in arms)


# ===================================================== supervisor / agent
class _ScriptedAgent(DSElasticAgent):
    """run() harness with a scripted rc sequence instead of subprocesses."""

    def __init__(self, rcs, **kw):
        super().__init__(["true"], {"elasticity": {"enabled": False}},
                         backoff_seconds=0.0, **kw)
        self._rcs = list(rcs)

    def discover_world_size(self):
        return 1

    def _launch(self, env):
        self._last_env = dict(env)
        return self._rcs.pop(0)


class TestServeHangAccounting:
    def test_rc219_is_its_own_restart_class(self):
        agent = _ScriptedAgent([SERVE_HANG_EXIT_CODE, SERVE_HANG_EXIT_CODE,
                                0], restart_limit=0)
        n0 = resilience_counters.get("serve_hang_restarts")
        assert agent.run() == 0
        # two serve hangs restarted for free (restart_limit 0 untouched)
        assert agent.serve_hang_count == 2 and agent.restart_count == 0
        assert resilience_counters.get("serve_hang_restarts") == n0 + 2
        assert agent._last_env["DSTPU_ELASTIC_SERVE_HANG_COUNT"] == "2"
        assert agent._last_env["DSTPU_ELASTIC_ATTEMPT"] == "2"

    def test_serve_hang_limit_bounds_streak(self):
        agent = _ScriptedAgent([SERVE_HANG_EXIT_CODE] * 5,
                               serve_hang_limit=2)
        assert agent.run() == SERVE_HANG_EXIT_CODE
        assert agent.serve_hang_count == 3  # 2 allowed + the one that broke

    def test_crash_resets_serve_hang_streak(self):
        agent = _ScriptedAgent(
            [SERVE_HANG_EXIT_CODE, 1, SERVE_HANG_EXIT_CODE, 0],
            restart_limit=2, serve_hang_limit=1)
        assert agent.run() == 0
        assert agent.serve_hang_count == 2 and agent.restart_count == 1

    def test_pod_rc_prefers_219_over_217(self):
        agent = _ScriptedAgent([0])
        rcs = {0: SERVE_HANG_EXIT_CODE, 1: 217}
        assert agent._pod_rc(rcs, dict(rcs)) == SERVE_HANG_EXIT_CODE
        rcs = {0: COMM_HANG_EXIT_CODE, 1: SERVE_HANG_EXIT_CODE}
        assert agent._pod_rc(rcs, dict(rcs)) == COMM_HANG_EXIT_CODE


class TestReplicaSupervisor:
    def test_drain_before_stop(self, tmp_path):
        """A drain request forwards SIGTERM to the worker, waits for a
        clean exit, writes the stopped health state and does NOT
        relaunch."""
        health = str(tmp_path / "health.json")
        # worker: exits 0 on SIGTERM (the drain contract), else sleeps
        sup = ReplicaSupervisor(
            [sys.executable, "-c",
             "import signal, sys, time;"
             "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0));"
             "time.sleep(60)"],
            restart_limit=3, health_file=health, drain_grace=10.0,
            poll_s=0.05)
        done = {}

        def run():
            done["rc"] = sup.run()

        t = threading.Thread(target=run)
        t.start()
        deadline = time.monotonic() + 10.0
        while not os.path.exists(health) and time.monotonic() < deadline:
            time.sleep(0.02)
        time.sleep(0.2)  # let the worker install its handler
        sup._drain_pending = True  # what the SIGTERM handler would store
        t.join(timeout=15.0)
        assert not t.is_alive() and done["rc"] == 0
        assert sup.drained
        h = json.load(open(health))
        assert h["state"] == "stopped"

    def test_worker_crash_restarts_then_succeeds(self, tmp_path):
        """First incarnation crashes, second succeeds (marker file), and
        the health probe passes through serving → restarting → stopped."""
        marker = str(tmp_path / "ran_once")
        health = str(tmp_path / "health.json")
        sup = ReplicaSupervisor(
            [sys.executable, "-c",
             f"import os, sys; p = {marker!r}\n"
             "if os.path.exists(p): sys.exit(0)\n"
             "open(p, 'w').close(); sys.exit(1)"],
            restart_limit=2, backoff_seconds=0.0, health_file=health,
            poll_s=0.02)
        assert sup.run() == 0
        assert sup.restart_count == 1
        assert json.load(open(health))["state"] == "stopped"

    def test_health_ready_tracks_heartbeat(self, tmp_path):
        from deepspeedsyclsupport_tpu.monitor.telemetry import Heartbeat

        hb_path = str(tmp_path / "heartbeat_rank0.json")
        health = str(tmp_path / "health.json")
        sup = ReplicaSupervisor(["true"], health_file=health,
                                heartbeat_file=hb_path,
                                heartbeat_timeout=5.0)
        sup._write_health("serving", 123)
        assert json.load(open(health))["ready"] is False  # no beat yet
        Heartbeat(hb_path).beat(1, force=True)
        sup._write_health("serving", 123)
        assert json.load(open(health))["ready"] is True

    def test_stale_heartbeat_flips_ready_false(self, tmp_path):
        """The fleet router's out-of-rotation gate: a heartbeat older than
        the watch timeout means the probe must answer NOT ready even while
        the worker process exists — a wedged replica keeps its pid."""
        hb_path = str(tmp_path / "heartbeat_rank0.json")
        health = str(tmp_path / "health.json")
        sup = ReplicaSupervisor(["true"], health_file=health,
                                heartbeat_file=hb_path,
                                heartbeat_timeout=2.0)
        # a beat stamped well past the timeout (another process's wall
        # clock by contract, so write the file directly)
        with open(hb_path, "w") as f:
            json.dump({"t": time.time() - 60.0, "step": 7,
                       "pid": 12345}, f)
        sup._write_health("serving", 123)
        h = json.load(open(health))
        assert h["state"] == "serving" and h["ready"] is False

    def test_drain_pending_flips_ready_false_before_exit(self, tmp_path):
        """During the drain window (SIGTERM seen, worker still finishing
        live streams) the probe must answer draining/NOT ready so a router
        steers new work away BEFORE the process exits."""
        health = str(tmp_path / "health.json")
        sup = ReplicaSupervisor(
            [sys.executable, "-c",
             "import signal, sys, time;"
             "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0));"
             "time.sleep(60)"],
            health_file=health, drain_grace=10.0, poll_s=0.02)
        done = {}

        def run():
            done["rc"] = sup.run()

        t = threading.Thread(target=run)
        t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                if json.load(open(health)).get("state") == "serving":
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.02)
        time.sleep(0.2)  # let the worker install its handler
        # hold the worker's reaping so the draining state is observable:
        # the drain path writes health BEFORE forwarding SIGTERM
        orig_write = sup._write_health
        seen = []

        def spy(state, pid, rc=None):
            orig_write(state, pid, rc)
            try:
                seen.append(json.load(open(health)))
            except (OSError, ValueError):
                pass

        sup._write_health = spy
        sup._drain_pending = True
        t.join(timeout=15.0)
        assert not t.is_alive() and done["rc"] == 0
        states = [(h["state"], h["ready"]) for h in seen]
        assert ("draining", False) in states  # out of rotation pre-exit
        assert states[-1] == ("stopped", False)

    def test_health_file_atomic_under_concurrent_reads(self, tmp_path):
        """The probe contract a load balancer relies on: the health file
        is rewritten via tmp+rename, so a concurrent reader always parses
        a COMPLETE record — never a torn one."""
        health = str(tmp_path / "health.json")
        sup = ReplicaSupervisor(["true"], health_file=health)
        sup._write_health("serving", 1)
        stop = threading.Event()
        torn = []
        reads = [0]

        def reader():
            while not stop.is_set():
                try:
                    with open(health) as f:
                        h = json.load(f)
                    assert "state" in h and "ready" in h
                    reads[0] += 1
                except FileNotFoundError:
                    pass
                except (ValueError, AssertionError) as e:
                    torn.append(repr(e))

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for th in threads:
            th.start()
        for i in range(300):
            sup._write_health("serving" if i % 2 else "draining", i)
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        assert not torn, torn[:3]
        assert reads[0] > 0


# ============================================================ chaos e2e
def _spec(tmp_path, name, gen=6, policy=None):
    jdir = str(tmp_path / f"j_{name}")
    os.makedirs(jdir, exist_ok=True)
    spec = {"model": "tiny", "dtype": "float32",
            "engine": {"dtype": "float32", "block_size": 8,
                       "max_context": 64, "max_tokens_per_batch": 16,
                       "max_sequences": 4},
            "journal_dir": jdir,
            "out": str(tmp_path / f"out_{name}.json"),
            "requests": [{"uid": u, "tokens": p, "max_new_tokens": gen}
                         for u, p in sorted(PROMPTS.items())]}
    if policy:
        spec["policy"] = policy
    path = str(tmp_path / f"spec_{name}.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    return path, spec


def _run_supervised(tmp_path, name, inject=None, policy=None, args=()):
    spec_path, spec = _spec(tmp_path, name, policy=policy)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if inject:
        env[ENV_SPEC] = json.dumps(inject)
    else:
        env.pop(ENV_SPEC, None)
    proc = subprocess.run(
        [sys.executable, "-m",
         "deepspeedsyclsupport_tpu.inference.v2.supervisor",
         "--spec", spec_path, "--backoff-seconds", "0.1", *args],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(spec["out"]) as f:
        return json.load(f), proc


@pytest.mark.slow
class TestServeChaosE2E:
    """The acceptance runs: a REAL supervisor process over a REAL engine
    worker process, with the fault injected through the environment.

    ``serve_crash``: the worker dies mid-decode (after ~7 emitted tokens,
    incarnation 0 only); the supervisor restarts it; the restarted worker
    replays every journaled in-flight stream from its watermark, and the
    final delivered token sequences are byte-identical to an
    uninterrupted supervised run — zero duplicate, zero missing tokens.

    ``decode_wedge``: the worker wedges inside an armed dispatch window;
    its stuck-decode watchdog converts the wedge into rc 219 within the
    deadline; the supervisor counts a serve hang (not a crash), restarts,
    and recovery completes identically."""

    def test_serve_crash_replay_token_equality(self, tmp_path):
        base, _ = _run_supervised(tmp_path, "base")
        assert base["recovery"]["replayed"] == []
        crash, proc = _run_supervised(
            tmp_path, "crash",
            inject={"serve_crash": {"tokens": 7, "attempt": 0}})
        assert crash["outputs"] == base["outputs"]
        assert sorted(crash["recovery"]["replayed"]) == sorted(
            int(u) for u in base["outputs"])
        assert crash["recovery_counters"]["replays"] == len(PROMPTS)
        assert crash["recovery"]["time_to_recover_s"] is not None
        log = proc.stdout + proc.stderr
        assert "crashing mid-decode" in log
        # every stream closed exactly once in the merged journal
        states, _ = load_journal(str(tmp_path / "j_crash"))
        assert all(st.closed for st in states.values())
        assert reconstruct_outputs(states) == {
            int(u): t for u, t in base["outputs"].items()}

    def test_decode_wedge_converts_to_rc219_within_deadline(self, tmp_path):
        policy = {"watchdog_enabled": True, "watchdog_deadline_s": 2.0,
                  "watchdog_poll_s": 0.1}
        base, _ = _run_supervised(tmp_path, "wbase", policy=policy)
        t0 = time.monotonic()
        wedge, proc = _run_supervised(
            tmp_path, "wedge", policy=policy,
            inject={"decode_wedge": {"round": 5, "attempt": 0}},
            args=("--serve-hang-limit", "2"))
        assert wedge["outputs"] == base["outputs"]
        log = proc.stdout + proc.stderr
        assert "rc=219" in log          # the watchdog's exit
        assert "stuck-decode hang (rc=219" in log  # agent class
        assert wedge["recovery_counters"]["replays"] == len(PROMPTS)
        # the wedge cost ~deadline, not a generic multi-minute timeout
        assert time.monotonic() - t0 < 300
