"""Serving fleet control plane suite (ISSUE 13).

Covers the tentpole pieces and their satellites:

* the router (``inference/v2/fleet/router.py``): fleet-edge admission
  (aggregate capacity projection, shedding before any replica queues),
  slack + affinity placement with sticky keys, health gating (draining /
  dead replicas out of rotation), ``Fleet/*`` strict-registry emission;
* journal-based cross-replica failover: an in-process replica kill whose
  journaled in-flight streams continue on survivors with final token
  sequences byte-identical to an uninterrupted run (the tier-1-safe twin
  of the multi-process chaos e2e), and the claim protocol's exactly-once
  arbitration between router failover and worker-local recovery;
* the process plane (``pool.py``): journal tailing, spool transport,
  health/dead decisions — unit-tested against synthetic files;
* ``tools/trace_report.py --fleet``: the merged cross-replica view
  renders from journal + router streams alone (login-node contract).

The real multi-process end-to-ends (3 supervised replica processes + the
router, a mid-decode ``serve_crash`` on one) are ``slow``-marked — each
pays several engine compiles in subprocesses.
"""
import json
import os
import sys
import time

import jax.numpy as jnp
import pytest

def teardown_module():
    # the engines built here install a world topology; drop it so later
    # modules start mesh-agnostic
    from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology

    reset_world_topology()


from deepspeedsyclsupport_tpu.inference.v2 import (  # noqa: E402
    InferenceEngineV2, ServingPolicyConfig, ServingSession, load_journal,
    reconstruct_outputs)
from deepspeedsyclsupport_tpu.inference.v2.fleet import (  # noqa: E402
    FleetConfig, FleetRequest, FleetRouter, LocalReplica, ProcessReplica,
    ReplicaEndpoint, claim_in_flight, claim_uids, read_claims)
from deepspeedsyclsupport_tpu.inference.v2.fleet.pool import (  # noqa: E402
    _JournalTail)
from deepspeedsyclsupport_tpu.inference.v2.fleet.router import (  # noqa: E402
    FleetEvent)
from deepspeedsyclsupport_tpu.inference.v2.supervisor import (  # noqa: E402
    RequestJournal, journal_path)
from deepspeedsyclsupport_tpu.models import build_model  # noqa: E402

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PROMPTS = {1: [7, 3, 11], 2: [4, 100, 42, 8, 19], 3: [9, 9, 2],
           4: [5, 6, 7, 8]}


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


def _local(tiny, rid, jdir=None):
    model, params = tiny
    policy = ServingPolicyConfig(
        journal_path=journal_path(jdir) if jdir else None)
    if jdir:
        os.makedirs(jdir, exist_ok=True)
    sess = ServingSession(_v2(model, params), policy)
    return LocalReplica(rid, sess, journal_dir=jdir)


def _drain(router, got=None, max_steps=800):
    steps = 0
    while not router.idle:
        events = router.poll()
        for ev in events:
            if got is not None and ev.kind == "token":
                got.setdefault(ev.uid, []).extend(ev.tokens)
        if not events:
            time.sleep(0.01)  # process replicas advance themselves
        steps += 1
        assert steps < max_steps, "fleet did not converge"


def _baseline(tiny, gen=6):
    model, params = tiny
    sess = ServingSession(_v2(model, params), ServingPolicyConfig())
    for uid, p in PROMPTS.items():
        assert sess.submit(uid, p, gen) == "admitted"
    out = {}
    while not sess.idle:
        for e in sess.step():
            if e.kind == "token":
                out.setdefault(e.uid, []).extend(e.tokens)
    return out


# ====================================================== router unit tests
class FakeReplica(ReplicaEndpoint):
    """Scriptable endpoint: outcomes and health are test-set knobs."""

    def __init__(self, rid, *, ready=True, draining=False, dead=False,
                 live=0, queued=0, max_live=8, submit_outcome="admitted",
                 replay_outcome="replayed", journal_dir=None):
        self.replica_id = rid
        self._ready, self._draining, self._dead = ready, draining, dead
        self._live, self._queued = live, queued
        self.max_live = max_live
        self.journal_dir = journal_dir
        self.submit_outcome = submit_outcome
        self.replay_outcome = replay_outcome
        self.submitted, self.replays, self.events = [], [], []

    def ready(self):
        return self._ready and not self._dead

    def draining(self):
        return self._draining

    def dead(self):
        return self._dead

    def load(self):
        return {"live": self._live, "queued": self._queued}

    def submit(self, req):
        self.submitted.append(req)
        self._live += 1
        return self.submit_outcome

    def replay(self, rr):
        self.replays.append(rr)
        return self.replay_outcome

    def poll_events(self):
        out, self.events = self.events, []
        return out


class TestRouterPlacement:
    def _router(self, reps, **cfg):
        cfg.setdefault("telemetry", False)
        return FleetRouter(reps, FleetConfig(**cfg))

    def test_least_loaded_wins(self):
        a = FakeReplica("a", live=5)
        b = FakeReplica("b", live=1)
        r = self._router([a, b], affinity="none")
        out, rid = r.submit(FleetRequest(uid=1, tokens=[1, 2],
                                         max_new_tokens=4))
        assert out == "routed" and rid == "b"
        assert b.submitted and not a.submitted

    def test_tenant_affinity_sticks_until_full(self):
        a, b = FakeReplica("a"), FakeReplica("b", max_live=2)
        r = self._router([a, b], affinity="tenant")
        _, first = r.submit(FleetRequest(uid=1, tokens=[1],
                                         max_new_tokens=4, tenant="t9"))
        # same tenant co-locates (prefix-reuse placement)...
        _, second = r.submit(FleetRequest(uid=2, tokens=[1],
                                          max_new_tokens=4, tenant="t9"))
        assert second == first
        assert r.counters["affinity_hits"] == 1
        # ...until the sticky target runs out of headroom
        sticky = r.replicas[first]
        sticky._live = sticky.max_live
        _, third = r.submit(FleetRequest(uid=3, tokens=[1],
                                         max_new_tokens=4, tenant="t9"))
        assert third != first

    def test_prompt_affinity_keys_on_prompt_head(self):
        a, b = FakeReplica("a", live=3), FakeReplica("b")
        r = self._router([a, b], affinity="prompt")
        _, first = r.submit(FleetRequest(uid=1, tokens=[5, 6, 7],
                                         max_new_tokens=4))
        _, second = r.submit(FleetRequest(uid=2, tokens=[5, 6, 7],
                                          max_new_tokens=4))
        assert second == first  # same prompt head → same replica
        assert r.counters["affinity_hits"] == 1

    def test_pluggable_placement(self):
        a, b = FakeReplica("a", live=9), FakeReplica("b")
        r = FleetRouter([a, b], FleetConfig(telemetry=False),
                        placement=lambda req, cands, sticky: "a")
        _, rid = r.submit(FleetRequest(uid=1, tokens=[1], max_new_tokens=2))
        assert rid == "a"

    def test_draining_and_dead_out_of_rotation(self):
        a = FakeReplica("a", draining=True)
        b = FakeReplica("b", dead=True)
        c = FakeReplica("c")
        r = self._router([a, b, c], affinity="none")
        assert r.rotation() == ["c"]
        _, rid = r.submit(FleetRequest(uid=1, tokens=[1], max_new_tokens=2))
        assert rid == "c"

    def test_duplicate_uid_rejected(self):
        r = self._router([FakeReplica("a")], affinity="none")
        r.submit(FleetRequest(uid=1, tokens=[1], max_new_tokens=2))
        with pytest.raises(ValueError, match="already routed"):
            r.submit(FleetRequest(uid=1, tokens=[1], max_new_tokens=2))


class TestEdgeAdmission:
    def test_no_ready_replica_sheds(self):
        r = FleetRouter([FakeReplica("a", ready=False)],
                        FleetConfig(telemetry=False))
        out, rid = r.submit(FleetRequest(uid=1, tokens=[1],
                                         max_new_tokens=2))
        assert (out, rid) == ("shed", None)
        assert r.counters["shed"] == 1

    def test_rate_unmeetable_sheds_at_edge(self):
        rep = FakeReplica("a")
        r = FleetRouter([rep], FleetConfig(telemetry=False))
        r.caps["a"].record_decode(1, 1.0)  # measured: 1 tok/s
        out, _ = r.submit(FleetRequest(uid=1, tokens=[1], max_new_tokens=4,
                                       rate_sla=100.0))
        assert out == "shed"
        assert not rep.submitted  # never reached a replica queue

    def test_ttft_unmeetable_sheds_at_edge(self):
        rep = FakeReplica("a")
        r = FleetRouter([rep], FleetConfig(telemetry=False))
        r.caps["a"].record_prefill(10, 10.0)  # measured: 1 tok/s prefill
        out, _ = r.submit(FleetRequest(uid=1, tokens=list(range(50)),
                                       max_new_tokens=4, ttft_sla_s=0.5))
        assert out == "shed"
        assert not rep.submitted

    def test_admission_none_routes_everything(self):
        rep = FakeReplica("a")
        r = FleetRouter([rep], FleetConfig(admission="none",
                                           telemetry=False))
        r.caps["a"].record_decode(1, 1.0)
        out, _ = r.submit(FleetRequest(uid=1, tokens=[1], max_new_tokens=4,
                                       rate_sla=100.0))
        assert out == "routed"


class TestRouterFailover:
    def test_dead_replica_streams_replay_on_survivor(self, tmp_path):
        jdir = str(tmp_path / "j")
        os.makedirs(jdir)
        j = RequestJournal(os.path.join(jdir, "journal_rank0.att0.jsonl"))
        j.admit(1, [1, 2, 3], 6)
        j.emit(1, [42, 43], 2)
        j.admit(2, [9, 9], 4)
        j.close_request(2, "done")
        j.close()
        dead = FakeReplica("dead", journal_dir=jdir)
        alive = FakeReplica("alive")
        r = FleetRouter([dead, alive], FleetConfig(telemetry=False))
        dead._dead = True
        events = r.poll()
        assert r.failover_counters == {"deaths": 1, "replays": 1,
                                       "replay_sheds": 0}
        assert len(alive.replays) == 1
        rr = alive.replays[0]
        assert (rr.uid, rr.tokens, rr.out) == (1, [1, 2, 3], [42, 43])
        assert not events  # a replayed stream continues silently
        # the closed stream (uid 2) was never replayed
        assert all(x.uid != 2 for x in alive.replays)

    def test_failover_with_no_survivors_sheds(self, tmp_path):
        jdir = str(tmp_path / "j")
        os.makedirs(jdir)
        j = RequestJournal(os.path.join(jdir, "journal_rank0.att0.jsonl"))
        j.admit(1, [1], 4)
        j.close()
        dead = FakeReplica("dead", journal_dir=jdir, dead=True)
        r = FleetRouter([dead], FleetConfig(telemetry=False))
        events = r.poll()
        assert [e.kind for e in events] == ["shed"]
        assert r.failover_counters["replay_sheds"] == 1

    def test_transport_lost_requests_resubmit_and_claim(self, tmp_path):
        dead = FakeReplica("dead", journal_dir=str(tmp_path / "jd"))
        alive = FakeReplica("alive", journal_dir=str(tmp_path / "ja"))
        os.makedirs(dead.journal_dir)
        os.makedirs(alive.journal_dir)
        r = FleetRouter([dead, alive], FleetConfig(telemetry=False))
        r.submit(FleetRequest(uid=7, tokens=[1, 2], max_new_tokens=4))
        assert dead.submitted or alive.submitted
        victim = "dead" if dead.submitted else "alive"
        survivor = alive if victim == "dead" else dead
        r.replicas[victim]._dead = True
        r.poll()
        # never journal-admitted → fresh resubmit on the survivor, and the
        # uid is CLAIMED so a respawned worker skips its stale spool file
        assert len(survivor.replays) == 1 and survivor.replays[0].out == []
        assert read_claims(r.replicas[victim].journal_dir).covers(7)

    def test_failover_rebases_routed_t_for_capacity_sampling(self):
        """A failed-over flight's prefill sample on the survivor must
        measure the RE-prefill, not the dead replica's whole lifetime —
        an inflated sample would crater the survivor's capacity model and
        edge-shed everything after the failover."""
        a = FakeReplica("a", journal_dir=None)
        b = FakeReplica("b")
        r = FleetRouter([a, b], FleetConfig(telemetry=False))
        t0 = r.clock()
        _, rid = r.submit(FleetRequest(uid=1, tokens=[1, 2, 3],
                                       max_new_tokens=8), now=t0 - 30.0)
        victim, survivor = (a, b) if rid == "a" else (b, a)
        victim.events.append(FleetEvent("token", 1, t0 - 29.0,
                                        replica_id=victim.replica_id,
                                        tokens=[5]))
        r.poll(now=t0 - 29.0)
        fl = r.flights[1]
        assert fl.first_token_t is not None
        victim._dead = True
        r.poll(now=t0)
        assert fl.replica_id == survivor.replica_id
        assert fl.first_token_t is None  # replay landing ≠ fresh TTFT
        assert fl.routed_t >= t0 - 1.0   # re-based: not the -30s original
        # the survivor's first token now records a sane prefill duration
        survivor.events.append(FleetEvent(
            "token", 1, t0 + 0.5, replica_id=survivor.replica_id,
            tokens=[5, 6]))
        r.poll(now=t0 + 0.5)
        assert r.caps[survivor.replica_id]._prefill.samples == 1
        assert r.caps[survivor.replica_id].prefill_tok_s > 1.0

    def test_mark_dead_is_idempotent(self):
        a = FakeReplica("a")
        b = FakeReplica("b")
        r = FleetRouter([a, b], FleetConfig(telemetry=False))
        assert r.mark_dead("a") == []
        assert r.mark_dead("a") == []
        assert r.failover_counters["deaths"] == 1


# ======================================================== claim protocol
class TestClaimProtocol:
    def _journal(self, jdir):
        os.makedirs(jdir, exist_ok=True)
        j = RequestJournal(os.path.join(jdir, "journal_rank0.att0.jsonl"))
        j.admit(1, [1, 2], 6)
        j.emit(1, [10], 1)
        j.admit(2, [3], 4)
        j.close_request(2, "done")
        j.close()

    def test_claim_returns_in_flight_once(self, tmp_path):
        jdir = str(tmp_path / "j")
        self._journal(jdir)
        first = claim_in_flight(jdir, claimer="router")
        assert sorted(first) == [1]  # uid 2 is closed
        assert first[1].out == [10]
        # exactly-once: a second pass (router restart) claims nothing
        assert claim_in_flight(jdir, claimer="router") == {}
        claim = read_claims(jdir)
        assert claim.covers(1) and not claim.covers(2)

    def test_claim_uids_extends(self, tmp_path):
        jdir = str(tmp_path / "j")
        os.makedirs(jdir)
        claim_uids(jdir, [5, 6], claimer="router")
        claim = read_claims(jdir)
        assert claim.covers(5) and claim.covers(6)
        claim_uids(jdir, [6, 7], claimer="router")
        assert read_claims(jdir).covers(7)

    def test_worker_recovery_skips_claimed(self, tiny, tmp_path):
        """The arbitration: once the router claims a stream, a restarted
        worker's recovery must not replay it (double-serve)."""
        from deepspeedsyclsupport_tpu.inference.v2 import recover_requests

        jdir = str(tmp_path / "j")
        self._journal(jdir)
        claim_in_flight(jdir, claimer="router")
        states, last_t = load_journal(jdir)
        claim = read_claims(jdir)
        recoverable = {u: st for u, st in states.items()
                       if not claim.covers(u)}
        model, params = tiny
        sess = ServingSession(_v2(model, params), ServingPolicyConfig())
        summary = recover_requests(sess, recoverable, last_t)
        assert summary["replayed"] == []  # uid 1 is claimed, uid 2 closed


# ============================================ in-process fleet failover
class TestFleetFailoverSmoke:
    """Tier-1-safe twin of the multi-process chaos e2e: LocalReplica kill
    → journal claim → replay on the survivor — byte-identical outputs."""

    def test_kill_mid_decode_fails_over_byte_identical(self, tiny,
                                                       tmp_path):
        base = _baseline(tiny)
        r0 = _local(tiny, "0", str(tmp_path / "replica0" / "journal"))
        r1 = _local(tiny, "1", str(tmp_path / "replica1" / "journal"))
        router = FleetRouter(
            [r0, r1],
            FleetConfig(affinity="none",
                        log_path=str(tmp_path / "router.jsonl")))
        for uid, p in PROMPTS.items():
            out, _ = router.submit(FleetRequest(uid=uid, tokens=p,
                                                max_new_tokens=6))
            assert out == "routed"
        got = {}
        killed = False
        steps = 0
        while not router.idle and steps < 800:
            for ev in router.poll():
                if ev.kind == "token":
                    got.setdefault(ev.uid, []).extend(ev.tokens)
            steps += 1
            if not killed and sum(len(v) for v in got.values()) >= 5:
                killed = True
                r0.kill()
        assert killed, "need a mid-decode kill point"
        router.close()
        assert router.failover_counters["deaths"] == 1
        assert router.failover_counters["replays"] >= 1
        # the journals are the delivery record: byte-identical to the
        # uninterrupted run, every stream closed exactly once fleet-wide
        states, _ = load_journal([r0.journal_dir, r1.journal_dir])
        assert reconstruct_outputs(states) == base
        assert all(st.closed for st in states.values())
        closes = 0
        for jdir in (r0.journal_dir, r1.journal_dir):
            for name in os.listdir(jdir):
                if not name.startswith("journal_rank"):
                    continue
                for line in open(os.path.join(jdir, name)):
                    if '"serve/close"' in line:
                        closes += 1
        assert closes == len(PROMPTS)
        r1.close()

    def test_fleet_registry_emission_strict(self, tiny, tmp_path):
        """``Fleet/*`` counters/gauges/quantiles validate against the
        strict registry (suite-wide DSTPU_STRICT_EVENTS=1)."""
        r0 = _local(tiny, "0")
        router = FleetRouter([r0], FleetConfig())
        out, _ = router.submit(FleetRequest(
            uid=1, tokens=PROMPTS[1], max_new_tokens=3))
        assert out == "routed"
        _drain(router)
        ev = dict((n, v) for n, v, _ in router.summary_events(step=1))
        assert ev["Fleet/routed"] == 1.0
        assert ev["Fleet/completed"] == 1.0
        assert ev["Fleet/replicas_ready"] == 1.0
        assert "Fleet/routed_ttft_s/p50" in ev
        r0.close()


# ========================================================= process plane
class TestJournalTail:
    def test_incremental_reads_with_torn_tail(self, tmp_path):
        jdir = str(tmp_path)
        path = os.path.join(jdir, "journal_rank0.att0.jsonl")
        tail = _JournalTail(jdir)
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "event", "name": "serve/admit",
                                "data": {"uid": 1}}) + "\n")
            f.write('{"kind": "event", "name": "serve/emi')  # torn
        recs = tail.read_new()
        assert [r["name"] for r in recs] == ["serve/admit"]
        with open(path, "a") as f:  # the torn line completes
            f.write('t", "data": {"uid": 1, "tokens": [5]}}\n')
        recs = tail.read_new()
        assert [r["name"] for r in recs] == ["serve/emit"]
        assert tail.read_new() == []  # nothing new → nothing returned


class TestProcessReplicaHealth:
    def _pr(self, tmp_path, **kw):
        return ProcessReplica("0", str(tmp_path / "r0"), {"model": "tiny"},
                              **kw)

    def _write_health(self, pr, state, ready, t=None):
        with open(pr.health_file, "w") as f:
            json.dump({"state": state, "ready": ready,
                       "t": time.time() if t is None else t}, f)

    def test_ready_requires_fresh_serving_probe(self, tmp_path):
        pr = self._pr(tmp_path, dead_after_s=5.0)
        assert not pr.ready()  # no probe at all
        self._write_health(pr, "serving", True)
        assert pr.ready()
        self._write_health(pr, "serving", True, t=time.time() - 60)
        assert not pr.ready()  # stale probe → out of rotation
        self._write_health(pr, "draining", True)
        assert not pr.ready() and pr.draining()

    def test_dead_on_stale_probe_not_while_expected_down(self, tmp_path):
        pr = self._pr(tmp_path, dead_after_s=0.5)
        self._write_health(pr, "serving", True, t=time.time() - 10)
        assert pr.dead()
        pr._expected_down = True  # drain/respawn in progress keeps streams
        assert not pr.dead()

    def test_spool_files_atomic_and_ordered(self, tmp_path):
        pr = self._pr(tmp_path)
        pr.submit(FleetRequest(uid=3, tokens=[1, 2], max_new_tokens=4,
                               tenant="t"))
        rr_names = sorted(os.listdir(pr.spool_dir))
        assert len(rr_names) == 1 and rr_names[0].endswith("_3.json")
        with open(os.path.join(pr.spool_dir, rr_names[0])) as f:
            rec = json.load(f)
        # spooled_t is the router-side ingestion stamp the worker turns
        # into the request's spool_wait stage (monitor/reqtrace.py)
        assert abs(time.time() - rec.pop("spooled_t")) < 60.0
        assert rec == {"uid": 3, "tokens": [1, 2], "max_new_tokens": 4,
                       "tenant": "t", "rate_sla": 0.0}
        assert not [n for n in os.listdir(pr.spool_dir) if ".tmp" in n]

    def test_poll_events_maps_journal_records(self, tmp_path):
        pr = self._pr(tmp_path)
        j = RequestJournal(os.path.join(pr.journal_dir,
                                        "journal_rank0.att0.jsonl"))
        j.admit(1, [1], 4)
        j.emit(1, [9, 8], 2)
        j.close_request(1, "done")
        j.admit(2, [2], 4)
        j.close_request(2, "replay_shed")
        j.close()
        evs = pr.poll_events()
        kinds = [(e.kind, e.uid) for e in evs]
        assert ("token", 1) in kinds
        assert ("finish", 1) in kinds
        assert ("shed", 2) in kinds
        assert pr.load() == {"live": 0, "queued": 0}  # all closed


# ===================================================== trace_report --fleet
def _load_trace_report():
    import importlib.util

    path = os.path.join(REPO, "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTraceReportFleet:
    def _fleet_root(self, tmp_path):
        root = str(tmp_path / "fleet")
        j0 = os.path.join(root, "replica0", "journal")
        j1 = os.path.join(root, "replica1", "journal")
        os.makedirs(j0)
        os.makedirs(j1)
        a = RequestJournal(os.path.join(j0, "journal_rank0.att0.jsonl"))
        a.admit(1, [1, 2], 4)
        a.emit(1, [7], 1)  # in flight at "death"
        a.close()
        time.sleep(0.02)
        b = RequestJournal(os.path.join(j1, "journal_rank0.att0.jsonl"))
        b.admit(1, [1, 2], 4, out=[7], replayed=True)
        b.emit(1, [8], 2)
        b.close_request(1, "done")
        b.admit(2, [5], 2)
        b.emit(2, [3], 1)
        b.close_request(2, "done")
        b.close()
        with open(os.path.join(j0, "failover_claim.json"), "w") as f:
            json.dump({"uids": {"1": "router"}, "stamped": [1.0]}, f)
        router = [{"kind": "meta", "name": "fleet/start", "t": 0.0},
                  {"kind": "event", "name": "fleet/route", "t": 0.5,
                   "data": {"uid": 1, "replica": "0"}},
                  {"kind": "event", "name": "fleet/route", "t": 0.6,
                   "data": {"uid": 2, "replica": "1"}},
                  {"kind": "event", "name": "fleet/death", "t": 2.0,
                   "data": {"replica": "0"}},
                  {"kind": "event", "name": "fleet/failover", "t": 2.1,
                   "data": {"uid": 1, "replica": "1",
                            "outcome": "replayed", "watermark": 1}},
                  {"kind": "dump", "t": 3.0,
                   "data": {"reason": "fleet_close", "metrics": {
                       "counters": {"Fleet/routed": 2,
                                    "Fleet/failover.replays": 1}}}}]
        with open(os.path.join(root, "router.jsonl"), "w") as f:
            for rec in router:
                f.write(json.dumps(rec) + "\n")
        return root

    def test_fleet_summary_renders_offline(self, tmp_path, capsys):
        root = self._fleet_root(tmp_path)
        tr = _load_trace_report()
        assert tr.main([root, "--fleet"]) == 0
        out = capsys.readouterr().out
        assert "fleet report — 2 replica(s)" in out
        assert "replica0: 1 request(s)" in out
        assert "1 replayed-in" in out
        assert "exactly one (exactly-once holds)" in out
        assert "1 death(s), 1 claimed stream(s), 1 replay(s)" in out
        assert "routed TTFT" in out
        assert "Fleet/failover.replays = 1" in out

    def test_fleet_summary_empty_input_exits_2(self, tmp_path, capsys):
        tr = _load_trace_report()
        assert tr.main([str(tmp_path), "--fleet"]) == 2

    def test_fleet_report_runs_with_jax_import_blocked(self, tmp_path):
        """The login-node contract: the --fleet view is stdlib-only."""
        import subprocess

        root = self._fleet_root(tmp_path)
        blocker = tmp_path / "nojax"
        blocker.mkdir()
        (blocker / "jax.py").write_text(
            "raise ImportError('jax blocked: trace_report must be "
            "stdlib-only')\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = str(blocker)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             root, "--fleet"],
            env=env, capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "fleet report" in out.stdout


# ============================================================ chaos e2e
def _fleet_spec(root, requests, env=None, n_replicas=3, timeout_s=420):
    return {
        "root": root, "n_replicas": n_replicas,
        "worker": {"model": "tiny", "dtype": "float32",
                   "engine": {"dtype": "float32", "block_size": 8,
                              "max_context": 64, "max_tokens_per_batch": 16,
                              "max_sequences": 4}},
        # a crashed replica STAYS dead: its streams must fail over to the
        # survivors (the headline), not wait out a local restart
        "supervisor_args": ["--restart-limit", "0",
                            "--backoff-seconds", "0.1"],
        "env": dict(env or {}),
        "router": {"affinity": "none", "dead_after_s": 1.5},
        "requests": requests,
        "out": os.path.join(root, "out.json"),
        "timeout_s": timeout_s}


@pytest.mark.slow
class TestFleetChaosE2E:
    """The acceptance run: a REAL 3-replica fleet (supervisor + worker
    processes) under a router, one replica killed mid-decode by an
    injected ``serve_crash`` — its journaled in-flight streams fail over
    to surviving replicas, final token sequences are byte-identical to an
    uninterrupted fleet run, every journal close is exactly-once
    fleet-wide, and the fleet keeps delivering through the fault."""

    PROMPTS = {1: [7, 3, 11], 2: [4, 100, 42, 8, 19], 3: [9, 9, 2],
               4: [5, 6, 7, 8], 5: [2, 4, 6], 6: [11, 12, 13, 14]}

    def test_replica_death_fails_over_byte_identical(self, tmp_path):
        from deepspeedsyclsupport_tpu.inference.v2.fleet.cli import (
            fleet_journal_files, run_fleet)

        reqs = [{"uid": u, "tokens": p, "max_new_tokens": 6}
                for u, p in sorted(self.PROMPTS.items())]
        base = run_fleet(_fleet_spec(str(tmp_path / "base"), reqs))
        assert base["router"]["failover_deaths"] == 0
        assert sorted(base["outputs"]) == [str(u) for u in
                                           sorted(self.PROMPTS)]
        crash = run_fleet(_fleet_spec(
            str(tmp_path / "crash"), reqs,
            env={"0": {"DSTPU_FAULT_INJECTION": json.dumps(
                {"serve_crash": {"tokens": 5, "attempt": 0}})}}))
        # byte-identical delivery despite the mid-decode death
        assert crash["outputs"] == base["outputs"]
        assert crash["router"]["failover_deaths"] == 1
        assert crash["router"]["failover_replays"] >= 1
        # nonzero goodput through the fault: every stream completed and
        # was closed terminally
        assert set(crash["closed"]) == set(crash["outputs"])
        assert all(r == "done" for r in crash["closed"].values())
        # exactly-once closes across the merged fleet journals
        close_counts = {}
        for jdir in fleet_journal_files(str(tmp_path / "crash"), 3):
            for name in os.listdir(jdir):
                if not name.startswith("journal_rank"):
                    continue
                for line in open(os.path.join(jdir, name)):
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("name") == "serve/close":
                        uid = rec["data"]["uid"]
                        close_counts[uid] = close_counts.get(uid, 0) + 1
        assert close_counts == {u: 1 for u in self.PROMPTS}
        # the dead replica's journal dir carries the router's claim
        claimed = read_claims(str(tmp_path / "crash" / "replica0"
                                  / "journal"))
        assert claimed.uids, "router never claimed the dead replica"
        # offline view agrees (merged cross-replica report)
        tr = _load_trace_report()
        report = tr.fleet_summary(str(tmp_path / "crash"))
        assert "exactly one (exactly-once holds)" in report
        assert "1 death(s)" in report

    def test_rolling_restart_keeps_fleet_available(self, tmp_path):
        """Pool lifecycle: drain→respawn one replica at a time while the
        router keeps serving; requests submitted after the restart land on
        the respawned generation and everything completes."""
        from deepspeedsyclsupport_tpu.inference.v2.fleet.cli import run_fleet
        from deepspeedsyclsupport_tpu.inference.v2.fleet.pool import (
            ProcessReplica, ReplicaPool)
        from deepspeedsyclsupport_tpu.inference.v2.fleet.router import (
            FleetConfig, FleetRequest, FleetRouter)

        root = str(tmp_path / "roll")
        replicas = [
            ProcessReplica(str(i), os.path.join(root, f"replica{i}"),
                           {"model": "tiny", "dtype": "float32",
                            "engine": {"dtype": "float32", "block_size": 8,
                                       "max_context": 64,
                                       "max_tokens_per_batch": 16,
                                       "max_sequences": 4}},
                           supervisor_args=["--restart-limit", "1",
                                            "--backoff-seconds", "0.1"],
                           dead_after_s=3.0)
            for i in range(2)]
        pool = ReplicaPool(replicas)
        router = FleetRouter(replicas, FleetConfig(affinity="none",
                                                   telemetry=False))
        pool.start()
        try:
            assert pool.wait_ready(timeout=240)
            for uid, p in ((1, [1, 2, 3]), (2, [4, 5])):
                out, _ = router.submit(FleetRequest(uid=uid, tokens=p,
                                                    max_new_tokens=4))
                assert out == "routed"
            _drain(router, max_steps=3000)
            gens0 = [r.generation for r in replicas]
            pool.rolling_restart(wait_ready_s=240)
            assert [r.generation for r in replicas] == \
                [g + 1 for g in gens0]
            assert sorted(router.rotation()) == ["0", "1"]
            for uid, p in ((3, [6, 7, 8]), (4, [9, 10])):
                out, _ = router.submit(FleetRequest(uid=uid, tokens=p,
                                                    max_new_tokens=4))
                assert out == "routed"
            _drain(router, max_steps=3000)
            assert router.counters["completed"] == 4
            assert router.failover_counters["deaths"] == 0
        finally:
            router.close()
            pool.stop(timeout=60)
