"""Pod-scale fault tolerance suite (ISSUE 9).

Covers the four tentpole pieces and their satellites:

* the collective hang watchdog (``comm/watchdog.py``): deadline arming,
  rc-218 fire path (stack dump + recorder flush + counter), warmup
  allowance for the compiling first step;
* the two-phase all-ranks checkpoint commit
  (``checkpoint/engine.py::pod_commit``): commit records, torn-pod
  detection, quarantine-by-sweep, never-resolved guarantees, the
  env-declared-pod polling barrier;
* rank-targeted comm-layer fault injection (hang / kill / tear-pod);
* the elastic agent's pod supervision: prompt sibling teardown, per-cause
  restart accounting (rc 218 vs 217 vs crash), restart-storm cap;
* the safe persistent compilation cache (staging + atomic publish) —
  the torn-write regression PR 1 root-caused;
* retry_io adoption in the NVMe swap path (failed IO re-issued, not fatal).

The real two-process elastic-agent end-to-end (hang → watchdog rc-218 →
prompt teardown → pod restart → bit-identical resume, with the torn pod
checkpoint the death leaves behind never being resolved) lives in
``TestPodElasticE2E`` and is marked ``slow`` — it launches six worker
processes and waits out a real watchdog deadline, which does not fit the
tier-1 wall clock. Everything else here is tier-1.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import zlib
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import deepspeedsyclsupport_tpu as dstpu
from deepspeedsyclsupport_tpu.checkpoint import ckpt_engine as ce
from deepspeedsyclsupport_tpu.checkpoint.engine import (
    COMMIT_FILE, DATA_FILE, find_latest_valid_tag, is_torn_pod, list_tags,
    load_latest_valid, pod_commit, pod_complete, rank_manifest_name,
    save_tree, verify_tree)
from deepspeedsyclsupport_tpu.comm.watchdog import (COMM_HANG_EXIT_CODE,
                                                   CollectiveWatchdog)
from deepspeedsyclsupport_tpu.monitor.monitor import resilience_counters
from deepspeedsyclsupport_tpu.monitor.telemetry import (FlightRecorder,
                                                        check_events,
                                                        is_declared)
from deepspeedsyclsupport_tpu.comm.watchdog import SERVE_HANG_EXIT_CODE
from deepspeedsyclsupport_tpu.runtime.resilience import (DIVERGENCE_EXIT_CODE,
                                                         PREEMPTION_EXIT_CODE)
from deepspeedsyclsupport_tpu.utils.compile_cache import (
    enable_safe_persistent_cache, publish_cache_entries, sweep_stale_staging)
from deepspeedsyclsupport_tpu.utils.fault_injection import (
    ENV_SPEC, FaultInjector, configure_fault_injection)
from deepspeedsyclsupport_tpu.utils.podid import pod_identity
from tests.unit.simple_model import SimpleModel, random_dataset, simple_config

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv(ENV_SPEC, raising=False)
    monkeypatch.delenv("DSTPU_POD_RANKS", raising=False)
    configure_fault_injection(None)
    resilience_counters.reset()
    yield
    configure_fault_injection(None)
    resilience_counters.reset()


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": rng.normal(size=(8, 8)).astype(np.float32)},
            "step": np.int32(seed)}


def _write_tag(save_dir, tag, seed, update_latest=True):
    state = _tree(seed)
    save_tree(str(save_dir / tag), state, {"global_steps": seed})
    if update_latest:
        ce._write_latest(str(save_dir / "latest"), tag)
    return state


def _fake_telemetry(dumps):
    rec = FlightRecorder(capacity=256)
    return SimpleNamespace(recorder=rec, dump=lambda reason: dumps.append(reason))


# ============================================================ pod identity
class TestPodIdentity:
    def test_solo_default(self):
        assert pod_identity() == (0, 1)

    def test_env_declared_pod(self, monkeypatch):
        monkeypatch.setenv("DSTPU_POD_RANKS", "4")
        monkeypatch.setenv("RANK", "2")
        assert pod_identity() == (2, 4)

    def test_malformed_env_degrades_to_solo(self, monkeypatch):
        monkeypatch.setenv("DSTPU_POD_RANKS", "many")
        assert pod_identity() == (0, 1)


# ================================================================ watchdog
class TestCollectiveWatchdog:
    def _watchdog(self, dumps, fired, tmp_path=None, **kw):
        kw.setdefault("deadline_s", 0.15)
        kw.setdefault("warmup_deadline_s", kw["deadline_s"])
        kw.setdefault("poll_s", 0.02)
        tele = _fake_telemetry(dumps)
        fired_evt = threading.Event()
        wd = CollectiveWatchdog(
            telemetry=tele,
            stack_path=(str(tmp_path / "stacks.txt") if tmp_path else None),
            exit_fn=lambda rc: (fired.append(rc), fired_evt.set()),
            **kw)
        return wd, tele, fired_evt

    def test_arm_disarm_cycle_never_fires(self, tmp_path):
        dumps, fired = [], []
        wd, tele, _evt = self._watchdog(dumps, fired, tmp_path)
        wd.start()
        try:
            for step in (1, 2, 3):
                wd.arm(step)
                wd.disarm(step)
            time.sleep(0.4)
            assert not fired
            arms = [r for r in tele.recorder.snapshot()
                    if r["name"] == "comm/arm"]
            assert [r["step"] for r in arms] == [1, 2, 3]
            assert all(r["data"]["deadline_s"] > 0 for r in arms)
        finally:
            wd.stop()

    def test_deadline_expiry_fires_rc218(self, tmp_path):
        dumps, fired = [], []
        wd, tele, evt = self._watchdog(dumps, fired, tmp_path)
        wd.start()
        try:
            wd.arm(7)
            assert evt.wait(5.0), "watchdog never fired"
        finally:
            wd.stop()
        assert fired == [COMM_HANG_EXIT_CODE]
        assert resilience_counters.get("comm_hang_aborts") == 1
        assert dumps == ["comm_hang"]  # flight recorder force-flushed
        hang = [r for r in tele.recorder.snapshot()
                if r["name"] == "comm/hang"]
        assert len(hang) == 1 and hang[0]["step"] == 7
        assert hang[0]["data"]["waited_s"] >= 0.15
        stacks = (tmp_path / "stacks.txt").read_text()
        assert "comm watchdog fired" in stacks
        assert "Thread" in stacks or "File" in stacks  # real tracebacks

    def test_warmup_deadline_covers_compiling_first_step(self):
        dumps, fired = [], []
        wd, _tele, evt = self._watchdog(dumps, fired, deadline_s=0.1,
                                        warmup_deadline_s=10.0)
        wd.start()
        try:
            wd.arm(1)           # first step: warmup allowance
            time.sleep(0.3)
            assert not fired    # 0.3s < 10s warmup
            wd.disarm(1)
            wd.arm(2)           # steady state: tight deadline
            assert evt.wait(5.0)
            assert fired == [COMM_HANG_EXIT_CODE]
        finally:
            wd.stop()

    def test_bad_deadline_rejected(self):
        with pytest.raises(ValueError):
            CollectiveWatchdog(deadline_s=0.0)


# ===================================================== comm fault injection
class TestCommFaultInjection:
    def test_hang_targets_rank_step_and_phase(self):
        fi = FaultInjector({"hang_step": {"rank": 1, "step": 3,
                                          "seconds": 0.15}})
        assert fi.armed
        assert not fi.maybe_hang_step(0, 3)      # wrong rank
        assert not fi.maybe_hang_step(1, 2)      # too early
        assert not fi.maybe_hang_step(1, 3, phase="in")  # wrong phase
        t0 = time.monotonic()
        assert fi.maybe_hang_step(1, 3)          # fires, blocks ~0.15s
        assert time.monotonic() - t0 >= 0.14
        assert not fi.maybe_hang_step(1, 4)      # one-shot

    def test_hang_phase_in(self):
        fi = FaultInjector({"hang_step": {"rank": 0, "step": 1,
                                          "phase": "in", "seconds": 0.05}})
        assert not fi.maybe_hang_step(0, 1)              # pre: no match
        assert fi.maybe_hang_step(0, 1, phase="in")      # in: fires

    def test_kill_is_one_shot_and_rank_targeted(self):
        fi = FaultInjector({"kill_step": {"rank": 1, "step": 2, "rc": 9}})
        assert fi.should_kill(0, 5) is None
        assert fi.should_kill(1, 1) is None
        assert fi.should_kill(1, 2) == 9
        assert fi.should_kill(1, 3) is None      # one-shot

    def test_tear_pod_skips_then_tears_commit(self, tmp_path):
        configure_fault_injection({"tear_pod": {"rank": 0, "skip": 1,
                                                "count": 1}})
        _write_tag(tmp_path, "s1", seed=1)       # skipped: stays complete
        _write_tag(tmp_path, "s2", seed=2)       # torn: commit deleted
        assert verify_tree(str(tmp_path / "s1"))[0]
        ok, reason = verify_tree(str(tmp_path / "s2"))
        assert not ok and "torn pod" in reason
        assert not (tmp_path / "s2" / COMMIT_FILE).exists()

    def test_tear_pod_rank_manifest_variant(self, tmp_path):
        configure_fault_injection({"tear_pod": {"rank": 0,
                                                "drop": "rank_manifest",
                                                "drop_rank": 0}})
        _write_tag(tmp_path, "s1", seed=1)
        ok, reason = verify_tree(str(tmp_path / "s1"))
        assert not ok and "manifest missing" in reason


# ============================================================== pod commit
class TestPodCommit:
    def test_save_tree_writes_commit_record(self, tmp_path):
        _write_tag(tmp_path, "s1", seed=3)
        tag = tmp_path / "s1"
        assert (tag / rank_manifest_name(0)).exists()
        commit = json.loads((tag / COMMIT_FILE).read_text())
        assert commit["world_size"] == 1
        assert commit["global_steps"] == 3
        rm_crc = zlib.crc32((tag / rank_manifest_name(0)).read_bytes())
        assert commit["ranks"] == {"0": rm_crc}
        assert pod_complete(str(tag)) == (True, "ok")
        assert resilience_counters.get("pod_commits") == 1

    def test_legacy_tag_without_protocol_is_complete(self, tmp_path):
        _write_tag(tmp_path, "s1", seed=1)
        (tmp_path / "s1" / COMMIT_FILE).unlink()
        (tmp_path / "s1" / rank_manifest_name(0)).unlink()
        ok, reason = pod_complete(str(tmp_path / "s1"))
        assert ok and "pre-pod-commit" in reason
        assert not is_torn_pod(str(tmp_path / "s1"))
        assert verify_tree(str(tmp_path / "s1"))[0]

    def test_torn_pod_never_resolved(self, tmp_path):
        """A tag whose commit record is missing (death between the phases)
        is skipped by every resolution walk — the prior tag is used."""
        _write_tag(tmp_path, "s1", seed=1)
        state2 = _write_tag(tmp_path, "s2", seed=2)  # latest -> s2
        (tmp_path / "s2" / COMMIT_FILE).unlink()     # torn pod
        assert is_torn_pod(str(tmp_path / "s2"))
        tag, skipped = find_latest_valid_tag(str(tmp_path))
        assert tag == "s1"
        assert any("torn pod" in reason for _t, reason in skipped)
        tag, state, _meta = load_latest_valid(
            str(tmp_path), {k: (v, jax.tree_util.tree_map(
                lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]),
                v)) for k, v in _tree(0).items()})
        assert tag == "s1"
        del state2

    def test_digest_mismatch_is_torn(self, tmp_path):
        _write_tag(tmp_path, "s1", seed=1)
        rm = tmp_path / "s1" / rank_manifest_name(0)
        rm.write_text(rm.read_text() + " ")
        ok, reason = pod_complete(str(tmp_path / "s1"))
        assert not ok and "digest" in reason

    def test_subset_committed_is_torn(self, tmp_path):
        """The exact ISSUE failure mode: commit record names 2 ranks, only
        rank 0's manifest landed."""
        _write_tag(tmp_path, "s1", seed=1)
        commit = json.loads((tmp_path / "s1" / COMMIT_FILE).read_text())
        commit["world_size"] = 2
        commit["ranks"]["1"] = 12345
        (tmp_path / "s1" / COMMIT_FILE).write_text(json.dumps(commit))
        ok, reason = pod_complete(str(tmp_path / "s1"))
        assert not ok and "rank 1 manifest missing" in reason

    def test_sweep_quarantines_torn_pod(self, tmp_path):
        _write_tag(tmp_path, "good", seed=1)
        _write_tag(tmp_path, "torn", seed=2, update_latest=False)
        (tmp_path / "torn" / COMMIT_FILE).unlink()
        handled = ce.sweep_staging_dirs(str(tmp_path))
        assert handled == 1
        assert not (tmp_path / "torn").exists()
        assert (tmp_path / "torn.corrupt").exists()  # forensic evidence
        assert resilience_counters.get("torn_pod_quarantined") == 1
        assert (tmp_path / "good").exists()          # complete tag untouched
        assert list_tags(str(tmp_path)) == ["good"]

    def test_env_pod_two_phase_polling_barrier(self, tmp_path, monkeypatch):
        """An env-declared pod of independent controllers: rank 1 publishes
        its phase-1 manifest; rank 0's phase 2 polls the shared directory
        and commits only once every expected manifest is present."""
        monkeypatch.setenv("DSTPU_POD_RANKS", "2")
        tag = tmp_path / "s5"
        # rank 1 saves first: manifest only, no payload, no commit
        monkeypatch.setenv("RANK", "1")
        save_tree(str(tag), _tree(5), {"global_steps": 5})
        assert (tag / rank_manifest_name(1)).exists()
        assert not (tag / DATA_FILE).exists()
        assert not (tag / COMMIT_FILE).exists()
        # rank 0 saves: payload + meta + manifest, then finds rank 1's
        # manifest already there and commits immediately
        monkeypatch.setenv("RANK", "0")
        save_tree(str(tag), _tree(5), {"global_steps": 5})
        commit = json.loads((tag / COMMIT_FILE).read_text())
        assert commit["world_size"] == 2
        assert sorted(commit["ranks"]) == ["0", "1"]
        assert pod_complete(str(tag))[0]
        assert verify_tree(str(tag))[0]

    def test_env_pod_commit_times_out_torn(self, tmp_path, monkeypatch):
        """Rank 0 alone in a declared 2-pod: the commit must NOT happen —
        the tag stays torn, which is the truth."""
        monkeypatch.setenv("DSTPU_POD_RANKS", "2")
        monkeypatch.setenv("RANK", "0")
        tag = tmp_path / "s6"
        t0 = time.monotonic()
        committed = pod_commit(_mk(tag), {"global_steps": 6}, timeout_s=0.3)
        assert not committed
        assert time.monotonic() - t0 >= 0.3
        assert not (tag / COMMIT_FILE).exists()
        assert is_torn_pod(str(tag))

    def test_stale_manifest_from_older_save_ignored(self, tmp_path,
                                                    monkeypatch):
        """A leftover rank manifest recording an older global_steps must
        not satisfy the commit barrier for a re-save of the same tag."""
        monkeypatch.setenv("DSTPU_POD_RANKS", "2")
        tag = tmp_path / "s7"
        monkeypatch.setenv("RANK", "1")
        save_tree(str(tag), _tree(1), {"global_steps": 1})  # old manifest
        monkeypatch.setenv("RANK", "0")
        committed = pod_commit(str(tag), {"global_steps": 2}, timeout_s=0.3)
        assert not committed  # rank 1's manifest is for step 1, not 2


def _mk(p):
    os.makedirs(str(p), exist_ok=True)
    return str(p)


# ================================================= engine torn-pod resume
class TestEngineTornPodResume:
    def _run(self, n, save_dir=None, save_at=()):
        engine, *_ = dstpu.initialize(model=SimpleModel(),
                                      config=simple_config())
        data = random_dataset(engine.train_batch_size(), n_batches=n, seed=7)
        losses = []
        for b in data:
            losses.append(float(engine.train_batch(b)["loss"]))
            if engine.global_steps in save_at:
                engine.save_checkpoint(str(save_dir))
        return engine, losses

    def test_resume_skips_torn_pod_bit_identical(self, tmp_path):
        # uninterrupted baseline
        _engine, ref_losses = self._run(4)

        self._run(4, save_dir=tmp_path, save_at=(2, 4))
        # the step-4 save "died between the phases": commit never written
        (tmp_path / "global_step4" / COMMIT_FILE).unlink()

        fresh, *_ = dstpu.initialize(model=SimpleModel(),
                                     config=simple_config())
        tag, _ = fresh.load_checkpoint(str(tmp_path))
        assert tag is not None and fresh.global_steps == 2
        # the torn tag was quarantined by the resume sweep, never resolved
        assert not (tmp_path / "global_step4").exists()
        assert (tmp_path / "global_step4.corrupt").exists()
        assert resilience_counters.get("torn_pod_quarantined") == 1
        data = random_dataset(fresh.train_batch_size(), n_batches=4, seed=7)
        resumed = [float(fresh.train_batch(b)["loss"]) for b in data[2:]]
        np.testing.assert_array_equal(resumed, ref_losses[2:])


# ========================================================== agent pod mode
class TestAgentPodMode:
    def _pod_agent(self, tmp_path, body, nprocs=2, **kw):
        """Worker whose behavior is a python expression over (rank,
        attempt); attempt counts per-rank launches via a marker file."""
        from deepspeedsyclsupport_tpu.elasticity import DSElasticAgent

        script = tmp_path / "worker.py"
        script.write_text(f"""
import os, sys, time
rank = int(os.environ["RANK"])
marker = os.path.join({str(tmp_path)!r}, f"attempts_{{rank}}")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
{body}
""")
        kw.setdefault("env", {"WORLD_SIZE": "8"})
        kw.setdefault("heartbeat_poll", 0.05)
        return DSElasticAgent([sys.executable, str(script)],
                              {"elasticity": {"enabled": False}},
                              nprocs=nprocs, **kw)

    def test_teardown_on_comm_hang_then_clean_restart(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("WORLD_SIZE", "8")
        body = """
# (both ranks have counted this attempt before either acts: on a loaded
# host a rank torn down BEFORE it wrote its marker would, relaunched, take
# its first attempt's branch again and sleep its 30 s whole)
t_end = time.time() + 10
while n == 0 and time.time() < t_end and not all(
        os.path.exists(marker[:-1] + str(r)) for r in range(2)):
    time.sleep(0.02)
if n == 0 and rank == 1:
    sys.exit(218)          # watchdog found a hung collective
if n == 0:
    time.sleep(30)         # rank 0 would cascade-wait without teardown
sys.exit(0)
"""
        agent = self._pod_agent(tmp_path, body, restart_limit=0,
                                comm_hang_limit=2, teardown_grace=1.0)
        t0 = time.monotonic()
        rc = agent.run()
        elapsed = time.monotonic() - t0
        assert rc == 0
        # prompt teardown: rank 0's 30s sleep was cut short
        assert elapsed < 20, f"teardown was not prompt ({elapsed:.1f}s)"
        assert agent.comm_hang_count == 1
        assert agent.teardown_count == 1
        assert agent.restart_count == 0  # rc 218 never bills restart_limit
        assert agent.launch_history[0]["comm_hang"]
        assert resilience_counters.get("comm_hang_restarts") == 1
        assert resilience_counters.get("pod_teardowns") == 1
        # the pod env was declared to the workers
        assert agent.nprocs == 2

    def test_preemption_exit_never_tears_down_siblings(self, tmp_path,
                                                       monkeypatch):
        """rc 217 means the scheduler SIGTERMed the whole pod: the
        siblings are writing their own emergency checkpoints and must be
        allowed to finish — teardown on 217 would tear the very saves the
        free-restart contract preserves."""
        monkeypatch.setenv("WORLD_SIZE", "8")
        body = """
if n == 0 and rank == 0:
    sys.exit(217)            # first rank out after its emergency save
if n == 0:
    time.sleep(1.5)          # sibling still writing ITS emergency save
    sys.exit(217)
sys.exit(0)
"""
        agent = self._pod_agent(tmp_path, body, restart_limit=0,
                                teardown_grace=0.2)
        assert agent.run() == 0
        assert agent.teardown_count == 0       # nobody was killed
        assert agent.preemption_count == 1     # classified as preemption
        assert resilience_counters.get("pod_teardowns") == 0

    def test_pod_rc_prefers_most_specific_cause(self, tmp_path):
        """Aggregation unit (process timing makes the live version racy):
        among SELF-exited ranks, rc 218 outranks 217 outranks a plain
        crash, and ranks reaped by our own teardown never attribute."""
        agent = self._pod_agent(tmp_path, "sys.exit(0)")
        rc = agent._pod_rc
        assert rc({0: 217, 1: 218}, {0: 217, 1: 218}) == COMM_HANG_EXIT_CODE
        assert rc({0: 1, 1: 217}, {0: 1, 1: 217}) == PREEMPTION_EXIT_CODE
        assert rc({0: 1, 1: 7}, {0: 1, 1: 7}) == 1
        # rank 1 died by our SIGTERM (not in self_exits): rank 0's cause
        # wins, and an all-healthy pod is 0
        assert rc({0: 218, 1: -15}, {0: 218}) == COMM_HANG_EXIT_CODE
        assert rc({0: 0, 1: 0}, {0: 0, 1: 0}) == 0
        # only our-kill rcs left (heartbeat-hang shape): surfaced non-zero
        assert rc({0: -15, 1: -15}, {}) == -15

    def test_comm_hang_limit_bounds_the_streak(self, tmp_path, monkeypatch):
        monkeypatch.setenv("WORLD_SIZE", "8")
        agent = self._pod_agent(tmp_path, "sys.exit(218)", nprocs=1,
                                restart_limit=5, comm_hang_limit=2)
        assert agent.run() == COMM_HANG_EXIT_CODE
        assert agent.comm_hang_count == 3  # limit + the exceeding attempt
        assert agent.restart_count == 0

    def test_storm_limit_caps_total_relaunches(self, tmp_path, monkeypatch):
        """Alternating free-restart causes dodge every per-class limit;
        the storm cap bounds their sum."""
        monkeypatch.setenv("WORLD_SIZE", "8")
        body = "sys.exit(217 if n % 2 == 0 else 218)"
        agent = self._pod_agent(tmp_path, body, nprocs=1, restart_limit=99,
                                storm_limit=3)
        rc = agent.run()
        assert rc in (PREEMPTION_EXIT_CODE, COMM_HANG_EXIT_CODE)
        assert len(agent.launch_history) == 4  # storm cap: 1 + 3 relaunches
        assert (agent.preemption_count + agent.comm_hang_count) == 3


# ===================================================== divergence restarts
class TestAgentDivergenceMode:
    """rc-220 accounting (ISSUE 16 satellite): the sentinel's divergence
    abort is its own restart class — never billed against ``restart_limit``,
    bounded by ``--divergence-limit``, streak-reset by other causes, and a
    teardown trigger like any self-failure (a diverged rank's siblings are
    about to all-reduce with poisoned state)."""

    def _pod_agent(self, tmp_path, body, nprocs=2, **kw):
        """Worker whose behavior is a python expression over (rank,
        attempt); attempt counts per-rank launches via a marker file."""
        from deepspeedsyclsupport_tpu.elasticity import DSElasticAgent

        script = tmp_path / "worker.py"
        script.write_text(f"""
import os, sys, time
rank = int(os.environ["RANK"])
marker = os.path.join({str(tmp_path)!r}, f"attempts_{{rank}}")
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
{body}
""")
        kw.setdefault("env", {"WORLD_SIZE": "8"})
        kw.setdefault("heartbeat_poll", 0.05)
        return DSElasticAgent([sys.executable, str(script)],
                              {"elasticity": {"enabled": False}},
                              nprocs=nprocs, **kw)

    def test_divergence_limit_bounds_the_streak(self, tmp_path, monkeypatch):
        """A run that re-diverges from its last-good checkpoint every time
        needs a human: the per-cause limit stops the loop and surfaces
        rc 220, with restart_limit untouched (the code didn't crash)."""
        monkeypatch.setenv("WORLD_SIZE", "8")
        agent = self._pod_agent(tmp_path, "sys.exit(220)", nprocs=1,
                                restart_limit=5, divergence_limit=2)
        assert agent.run() == DIVERGENCE_EXIT_CODE
        assert agent.divergence_count == 3  # limit + the exceeding attempt
        assert agent.restart_count == 0     # rc 220 never bills restart_limit
        assert resilience_counters.get("divergence_restarts") == 3

    def test_other_causes_reset_the_divergence_streak(self, tmp_path,
                                                      monkeypatch):
        """divergence → preemption → divergence → clean: each 220 is a
        streak of ONE (the intervening 217 reset it), so divergence_limit=1
        never trips and the run converges to 0."""
        monkeypatch.setenv("WORLD_SIZE", "8")
        body = "sys.exit([220, 217, 220, 0][min(n, 3)])"
        agent = self._pod_agent(tmp_path, body, nprocs=1, restart_limit=0,
                                divergence_limit=1, storm_limit=10)
        assert agent.run() == 0
        assert agent.divergence_count == 2
        assert agent.preemption_count == 1
        assert agent.restart_count == 0
        assert [h["divergence"] for h in agent.launch_history] == \
            [True, False, True, False]
        assert [h["preempted"] for h in agent.launch_history] == \
            [False, True, False, False]
        assert resilience_counters.get("divergence_restarts") == 2

    def test_pod_rc_ranks_divergence_between_hangs_and_preemption(
            self, tmp_path):
        """Aggregation unit: among self-exited ranks, hang causes (218/219
        — infrastructure) outrank divergence (220 — the model), which
        outranks clean preemption (217) and plain crashes."""
        agent = self._pod_agent(tmp_path, "sys.exit(0)")
        rc = agent._pod_rc
        assert rc({0: 217, 1: 220}, {0: 217, 1: 220}) == DIVERGENCE_EXIT_CODE
        assert rc({0: 220, 1: 218}, {0: 220, 1: 218}) == COMM_HANG_EXIT_CODE
        assert rc({0: 219, 1: 220}, {0: 219, 1: 220}) == SERVE_HANG_EXIT_CODE
        assert rc({0: 220, 1: 1}, {0: 220, 1: 1}) == DIVERGENCE_EXIT_CODE
        # the diverged rank was reaped by our teardown SIGTERM (not a
        # self-exit): the surviving self-exit cause attributes instead
        assert rc({0: 220, 1: -15}, {0: 220}) == DIVERGENCE_EXIT_CODE

    def test_divergence_count_exported_to_workers(self, tmp_path,
                                                  monkeypatch):
        """Workers see how many divergence restarts preceded them (e.g. to
        widen logging or cut LR on the second attempt)."""
        monkeypatch.setenv("WORLD_SIZE", "8")
        out = tmp_path / "seen_count"
        body = f"""
if n == 0:
    sys.exit(220)
open({str(out)!r}, "w").write(os.environ["DSTPU_ELASTIC_DIVERGENCE_COUNT"])
sys.exit(0)
"""
        agent = self._pod_agent(tmp_path, body, nprocs=1, restart_limit=0,
                                divergence_limit=3)
        assert agent.run() == 0
        assert out.read_text() == "1"

    def test_divergence_tears_down_siblings_promptly(self, tmp_path,
                                                     monkeypatch):
        """One rank's sentinel aborts with 220 ⇒ its siblings' next
        collective would hang on poisoned state until the watchdog's
        deadline — teardown now, attribute to divergence, and never
        misattribute the SIGTERMed siblings as crashes."""
        monkeypatch.setenv("WORLD_SIZE", "8")
        body = """
# (both ranks have counted this attempt before either acts: on a loaded
# host a rank torn down BEFORE it wrote its marker would, relaunched, take
# its first attempt's branch again and sleep its 30 s whole)
t_end = time.time() + 10
while n == 0 and time.time() < t_end and not all(
        os.path.exists(marker[:-1] + str(r)) for r in range(2)):
    time.sleep(0.02)
if n == 0 and rank == 0:
    sys.exit(220)          # sentinel: ladder exhausted
if n == 0:
    time.sleep(30)         # sibling would cascade-wait without teardown
sys.exit(0)
"""
        agent = self._pod_agent(tmp_path, body, restart_limit=0,
                                divergence_limit=2, teardown_grace=1.0)
        t0 = time.monotonic()
        rc = agent.run()
        elapsed = time.monotonic() - t0
        assert rc == 0
        assert elapsed < 20, f"teardown was not prompt ({elapsed:.1f}s)"
        assert agent.divergence_count == 1
        assert agent.teardown_count == 1
        assert agent.restart_count == 0
        assert agent.launch_history[0]["divergence"]
        assert resilience_counters.get("divergence_restarts") == 1
        assert resilience_counters.get("pod_teardowns") == 1


# ========================================================== compile cache
class TestSafeCompileCache:
    def test_seed_publish_atomic(self, tmp_path):
        shared = tmp_path / "cache"
        shared.mkdir()
        (shared / "entry_a").write_bytes(b"compiled-a")
        # a publisher killed mid-copy left a torn temp: never an entry
        (shared / ".pub-999999-entry_b").write_bytes(b"half")
        staging = enable_safe_persistent_cache(str(shared),
                                               configure_jax=False)
        assert os.path.isfile(os.path.join(staging, "entry_a"))
        assert not any(n.startswith(".pub") for n in os.listdir(staging))
        # this process compiles something new...
        with open(os.path.join(staging, "entry_c"), "wb") as f:
            f.write(b"compiled-c" * 1000)
        n = publish_cache_entries(staging, str(shared))
        assert n == 1
        assert (shared / "entry_c").read_bytes() == b"compiled-c" * 1000
        # publish left no torn temps behind for the published entry
        assert not any(n.startswith(".pub") and "entry_c" in n
                       for n in os.listdir(shared))
        # idempotent: re-publish finds nothing new
        assert publish_cache_entries(staging, str(shared)) == 0

    def test_torn_write_pattern_regression(self, tmp_path):
        """The PR 1 failure mode: a reader must never observe a partially
        written cache entry. With staging + atomic rename, the shared dir
        only ever contains full entries (and ignorable dotfiles)."""
        shared = tmp_path / "cache"
        shared.mkdir()
        st1 = enable_safe_persistent_cache(str(shared), configure_jax=False)
        st2 = enable_safe_persistent_cache(str(shared), configure_jax=False)
        payload = b"x" * 4096
        for st in (st1, st2):  # two concurrent writers, same entry name
            with open(os.path.join(st, "entry"), "wb") as f:
                f.write(payload)
        publish_cache_entries(st1, str(shared))
        publish_cache_entries(st2, str(shared))  # loser: already exists
        entries = [n for n in os.listdir(shared) if not n.startswith(".")]
        assert entries == ["entry"]
        assert (shared / "entry").read_bytes() == payload

    def test_stale_staging_swept(self, tmp_path):
        shared = tmp_path / "cache"
        shared.mkdir()
        dead = subprocess.Popen([sys.executable, "-c", "pass"])
        assert dead.wait() == 0  # reaped: the pid is conclusively dead
        stale_dir = shared / f".proc-{dead.pid}-deadbeef"
        stale_dir.mkdir()
        (shared / f".pub-{dead.pid}-leftover").write_bytes(b"torn")
        live_dir = shared / f".proc-{os.getpid()}-alive123"
        live_dir.mkdir()
        removed = sweep_stale_staging(str(shared))
        assert removed == 2
        assert not stale_dir.exists()
        assert live_dir.exists()  # our own staging is untouched


# ============================================================== swap retry
class TestSwapRetryIO:
    def test_injected_write_failures_self_heal(self, tmp_path):
        from deepspeedsyclsupport_tpu.runtime.swap_tensor import (
            AsyncTensorSwapper)

        configure_fault_injection({"write_fail": {"match": ".swp",
                                                  "count": 2}})
        sw = AsyncTensorSwapper(str(tmp_path / "nvme"))
        try:
            data = np.arange(1024, dtype=np.float32)
            sw.swap_out("opt/m", data)  # submit retried past 2 failures
            got = sw.retrieve("opt/m")
            np.testing.assert_array_equal(got, data)
            assert resilience_counters.get("io_retries") >= 2
        finally:
            sw.close()

    def test_failed_read_submit_retried(self, tmp_path):
        """The pread SUBMISSION is retried too — a transient submit
        failure must not kill the prefetching step (review finding)."""
        from deepspeedsyclsupport_tpu.runtime.swap_tensor import (
            AsyncTensorSwapper)

        sw = AsyncTensorSwapper(str(tmp_path / "nvme"))
        try:
            data = np.arange(32, dtype=np.float32) + 7
            sw.swap_out("x", data)
            sw.synchronize()
            real_pread = sw.handle.pread
            fails = {"left": 1}

            def flaky_pread(path, arr, offset=0):
                if fails["left"] > 0:
                    fails["left"] -= 1
                    raise OSError(11, "injected submit failure")
                return real_pread(path, arr, offset)

            sw.handle.pread = flaky_pread
            np.testing.assert_array_equal(sw.retrieve("x"), data)
            assert resilience_counters.get("io_retries") >= 1
        finally:
            sw.handle.pread = real_pread
            sw.close()

    def test_failed_read_reissued(self, tmp_path):
        from deepspeedsyclsupport_tpu.runtime.swap_tensor import (
            AsyncTensorSwapper)

        sw = AsyncTensorSwapper(str(tmp_path / "nvme"))
        try:
            data = np.arange(64, dtype=np.float32) * 3
            sw.swap_out("a/b", data)
            sw.synchronize()
            real_wait = sw.handle.wait
            fails = {"left": 1}

            def flaky_wait(req):
                real_wait(req)  # reap the real request either way
                if fails["left"] > 0:
                    fails["left"] -= 1
                    raise OSError(5, "injected wait failure")

            sw.handle.wait = flaky_wait
            got = sw.retrieve("a/b")  # first wait fails; read re-issued
            np.testing.assert_array_equal(got, data)
            assert resilience_counters.get("io_retries") >= 1
        finally:
            sw.handle.wait = real_wait
            sw.close()


# ===================================================== host scaler parity
class TestHostLossScaleParity:
    def test_host_state_machine_matches_jitted(self):
        """The multihost CPU-Adam path now runs loss scaling on host
        (fixing the last baselined host-sync debt); its transition must
        stay bit-identical to the jitted one over overflow bursts, scale
        growth and the hysteresis window."""
        from deepspeedsyclsupport_tpu.runtime.loss_scaler import (
            host_loss_scale_state, host_update_loss_scale, init_loss_scale,
            update_loss_scale)

        kw = dict(dynamic=True, scale_window=3, min_scale=1.0, hysteresis=2)
        dev = init_loss_scale(2 ** 10, dynamic=True, hysteresis=2)
        host = host_loss_scale_state(dev)
        pattern = [True, True, False, False, False, True, True, True,
                   True, True, True, False, True, True, True, True]
        for finite in pattern:
            dev = update_loss_scale(dev, jax.numpy.asarray(finite), **kw)
            host = host_update_loss_scale(host, finite, **kw)
            for a, b in zip(dev, host):
                assert float(a) == float(b), (pattern, dev, host)
        assert not isinstance(host.scale, jax.Array)  # stays host-resident

    def test_static_scaler_counts_overflows_only(self):
        from deepspeedsyclsupport_tpu.runtime.loss_scaler import (
            host_loss_scale_state, host_update_loss_scale, init_loss_scale)

        s = host_loss_scale_state(init_loss_scale(128.0, dynamic=False))
        s = host_update_loss_scale(s, False, dynamic=False, scale_window=5)
        assert float(s.scale) == 128.0 and int(s.overflows) == 1


# ========================================================== event registry
class TestPodEventRegistry:
    def test_new_resilience_and_commit_events_declared(self):
        for name in ("Resilience/comm_hang_aborts",
                     "Resilience/comm_hang_restarts",
                     "Resilience/pod_teardowns",
                     "Resilience/pod_commits",
                     "Resilience/torn_pod_quarantined",
                     "Ckpt/pod_commit_s",
                     "Pod/comm_hang.step", "Pod/comm_hang.culprit_rank"):
            assert is_declared(name), name
        # strict mode (on under the suite) must accept them end to end
        check_events([("Resilience/comm_hang_aborts", 1, 0),
                      ("Ckpt/pod_commit_s", 0.01, 0)])


# ====================================================== hang attribution
def _load_pod_module():
    path = os.path.join(REPO, "deepspeedsyclsupport_tpu", "monitor", "pod.py")
    spec = importlib.util.spec_from_file_location("_pod_for_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stream(pod, rank, records, path="mem"):
    base = [{"kind": "meta", "name": "flight_recorder/start", "t": 0.0,
             "seq": 0, "data": {"rank": rank, "pid": 1000 + rank}},
            {"kind": "meta", "name": "align/anchor", "t": 100.0, "seq": 1,
             "data": {"anchor": 1, "synced": True}}]
    return pod.RankStream(rank=rank, path=f"{path}_rank{rank}.jsonl",
                          records=base + records, truncated=False)


def _span(step, t, dur=0.01):
    return {"kind": "span", "name": "step", "step": step, "t": t,
            "dur": dur, "data": {"sync": 1}}


def _arm(step, t, rank, deadline=5.0):
    return {"kind": "event", "name": "comm/arm", "step": step, "t": t,
            "data": {"deadline_s": deadline, "rank": rank}}


class TestCommHangAttribution:
    def test_never_arrived_rank_named(self):
        pod = _load_pod_module()
        # rank 0 armed step 3 and waited (hang event); rank 1 armed 1-2
        # and NEVER armed 3: it is the rank the pod waited for
        r0 = [_arm(1, 101, 0), _span(1, 101.1), _arm(2, 102, 0),
              _span(2, 102.1), _arm(3, 103, 0),
              {"kind": "event", "name": "comm/hang", "step": 3, "t": 110,
               "data": {"waited_s": 6.2, "deadline_s": 5.0, "rank": 0}}]
        r1 = [_arm(1, 101, 1), _span(1, 101.1), _arm(2, 102, 1),
              _span(2, 102.1)]
        report = pod.fuse_pod({0: _stream(pod, 0, r0),
                               1: _stream(pod, 1, r1)})
        h = report.comm_hang
        assert h is not None and h["step"] == 3
        assert h["culprit_rank"] == 1
        assert h["culprit_reason"] == "never-arrived"
        assert h["arrived_ranks"] == [0]
        assert h["detected_by_ranks"] == [0]
        assert h["waited_s"] == pytest.approx(6.2)
        rendered = report.render()
        assert "collective hang" in rendered and "rank1" in rendered
        assert pod.validate_pod_report(report.to_dict()) == []

    def test_armed_but_never_completed_rank_named(self):
        pod = _load_pod_module()
        # both ranks armed step 3; rank 0 completed it, rank 1 wedged
        # inside (its own watchdog fired): never-completed attribution
        r0 = [_arm(3, 103, 0), _span(3, 103.1)]
        r1 = [_arm(3, 103.05, 1),
              {"kind": "event", "name": "comm/hang", "step": 3, "t": 110,
               "data": {"waited_s": 5.5, "deadline_s": 5.0, "rank": 1}}]
        report = pod.fuse_pod({0: _stream(pod, 0, r0),
                               1: _stream(pod, 1, r1)})
        h = report.comm_hang
        assert h["culprit_rank"] == 1
        assert h["culprit_reason"] == "never-completed"
        assert h["stuck_ranks"] == [1]

    def test_all_stuck_falls_back_to_last_to_arm(self):
        pod = _load_pod_module()
        r0 = [_arm(2, 102, 0), _span(2, 102.1), _arm(3, 103.0, 0)]
        r1 = [_arm(2, 102, 1), _span(2, 102.1), _arm(3, 103.4, 1)]
        report = pod.fuse_pod({0: _stream(pod, 0, r0),
                               1: _stream(pod, 1, r1)})
        h = report.comm_hang
        assert h is not None and h["step"] == 3
        assert h["culprit_rank"] == 1
        assert h["culprit_reason"] == "last-to-arm"
        assert h["arm_skew_s"] == pytest.approx(0.4, abs=1e-3)

    def test_healthy_run_reports_none(self):
        pod = _load_pod_module()
        r0 = [_arm(1, 101, 0), _span(1, 101.1)]
        report = pod.fuse_pod({0: _stream(pod, 0, r0)})
        assert report.comm_hang is None
        assert report.to_dict()["comm_hang"] is None

    def test_stepless_hang_event_never_crashes_the_merge(self):
        """A salvaged/torn stream can hold a comm/hang record that lost
        its step field; the offline merge must degrade, not raise."""
        pod = _load_pod_module()
        r0 = [_arm(1, 101, 0), _span(1, 101.1),
              {"kind": "event", "name": "comm/hang", "t": 110,
               "data": {"rank": 0}}]
        report = pod.fuse_pod({0: _stream(pod, 0, r0)})
        h = report.comm_hang
        assert h is not None and h["step"] is None
        assert h["detected_by_ranks"] == [0]
        assert report.events() and pod.validate_pod_report(
            report.to_dict()) == []
        report.render()  # no crash


# ================================================================ check_ckpt
def _load_check_ckpt():
    path = os.path.join(REPO, "tools", "check_ckpt.py")
    spec = importlib.util.spec_from_file_location("check_ckpt_pod", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCheckCkptPodVerdict:
    def test_verdicts(self, tmp_path, capsys):
        check_ckpt = _load_check_ckpt()
        _write_tag(tmp_path, "complete", seed=1)
        _write_tag(tmp_path, "torn", seed=2, update_latest=False)
        (tmp_path / "torn" / COMMIT_FILE).unlink()
        _write_tag(tmp_path, "legacy", seed=3, update_latest=False)
        (tmp_path / "legacy" / COMMIT_FILE).unlink()
        (tmp_path / "legacy" / rank_manifest_name(0)).unlink()
        rc = check_ckpt.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 1  # the torn tag fails the dir check
        assert "pod: COMPLETE (all 1 rank(s) committed)" in out
        assert "pod: TORN" in out and "no rank will ever resolve" in out
        assert "pod: n/a (pre-pod-commit tag" in out


# ========================================================= 2-process e2e
WORKER = r'''
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, os.environ["DSTPU_REPO"])
sys.path.insert(0, os.path.join(os.environ["DSTPU_REPO"], "tests"))
import deepspeedsyclsupport_tpu as ds
from unit.simple_model import SimpleModel, simple_config, random_dataset

rank = int(os.environ.get("RANK", "0"))
attempt = int(os.environ.get("DSTPU_ELASTIC_ATTEMPT", "0"))
if attempt > 0:
    # restarted incarnation: the injected fault must not replay
    from deepspeedsyclsupport_tpu.utils.fault_injection import (
        configure_fault_injection)
    configure_fault_injection({})

ckpt = os.environ["CKPT_DIR"]
out_dir = os.environ["OUT_DIR"]
tele = os.path.join(os.environ["TELE_DIR"], f"att{attempt}")
cfg = simple_config(telemetry={
    "enabled": True, "output_dir": tele,
    # flush every record: a torn-down sibling's stream must still carry
    # its last arm/span marks for the pod report's hang attribution
    "flush_interval_records": 1,
    "watchdog": {"enabled": True,
                 "deadline_s": float(os.environ.get("WD_DEADLINE", "10")),
                 "warmup_deadline_s": 600.0, "poll_s": 0.1}})
engine, *_ = ds.initialize(model=SimpleModel(hidden_dim=16), config=cfg)
tag, _ = engine.load_checkpoint(ckpt)
os.makedirs(out_dir, exist_ok=True)
log = open(os.path.join(out_dir, f"losses_rank{rank}_att{attempt}.jsonl"),
           "w")
log.write(json.dumps({"resumed": tag and os.path.basename(tag),
                      "start_step": engine.global_steps}) + "\n")
log.flush()
data = random_dataset(engine.train_batch_size(), hidden_dim=16,
                      n_batches=8, seed=11)
for b in data[engine.global_steps:]:
    m = engine.train_batch(b)
    loss = float(np.asarray(jax.device_get(m["loss"])))
    log.write(json.dumps({"step": engine.global_steps,
                          "loss_hex": loss.hex()}) + "\n")
    log.flush()
    if engine.global_steps == 4:
        engine.save_checkpoint(ckpt)
engine.save_checkpoint(ckpt)  # the final save: both ranks must commit
log.write(json.dumps({"done": True}) + "\n")
log.close()
'''


@pytest.mark.slow
class TestPodElasticE2E:
    """The acceptance run: a real two-process pod under the elastic agent.

    Incarnation 1: rank 1 arms step 6's collective window and wedges
    (injected ``hang_step`` with ``phase: "in"``); its watchdog fires
    rc 218 within the deadline. Rank 0 meanwhile finished its steps and is
    *blocked inside the final save's commit barrier polling for rank 1's
    manifest* — the agent's prompt teardown cuts that wait short instead
    of letting it run out the 90s commit timeout. The death leaves a
    genuinely torn pod tag on disk (rank 0's payload + manifest, no
    commit record). Incarnation 2: both ranks resume from the newest
    POD-COMPLETE tag (step 4 — the torn step-8 tag is quarantined, never
    resolved), finish, and the final save commits. The resumed losses must
    bit-match an uninterrupted baseline pod run.
    """

    def _run_pod(self, tmp_path, name, inject=None, deadline="10"):
        from deepspeedsyclsupport_tpu.elasticity import DSElasticAgent

        worker = tmp_path / f"worker_{name}.py"
        worker.write_text(WORKER)
        env = {
            "WORLD_SIZE": "8",
            "DSTPU_REPO": REPO,
            "CKPT_DIR": str(tmp_path / f"ckpt_{name}"),
            "OUT_DIR": str(tmp_path / f"out_{name}"),
            "TELE_DIR": str(tmp_path / f"tele_{name}"),
            "WD_DEADLINE": deadline,
            "DSTPU_POD_COMMIT_TIMEOUT_S": "90",
            "DSTPU_STRICT_EVENTS": "1",
        }
        if inject:
            env[ENV_SPEC] = json.dumps(inject)
        agent = DSElasticAgent([sys.executable, str(worker)],
                               {"elasticity": {"enabled": False}},
                               nprocs=2, restart_limit=1, comm_hang_limit=2,
                               storm_limit=4, teardown_grace=3.0, env=env,
                               heartbeat_poll=0.1)
        return agent

    def _losses(self, tmp_path, name, rank, attempt):
        p = (tmp_path / f"out_{name}"
             / f"losses_rank{rank}_att{attempt}.jsonl")
        lines = [json.loads(ln) for ln in p.read_text().splitlines()]
        head = lines[0]
        return head, {d["step"]: d["loss_hex"] for d in lines
                      if "step" in d}

    def test_hang_watchdog_teardown_restart_bitmatch(self, tmp_path):
        # ---------------- uninterrupted baseline pod run
        base = self._run_pod(tmp_path, "base")
        assert base.run() == 0
        assert base.comm_hang_count == 0
        _head, ref = self._losses(tmp_path, "base", rank=0, attempt=0)
        assert sorted(ref) == list(range(1, 9))

        # ---------------- fault-injected pod run
        agent = self._run_pod(
            tmp_path, "hang",
            inject={"hang_step": {"rank": 1, "step": 6, "phase": "in",
                                  "seconds": 600}})
        t0 = time.monotonic()
        rc = agent.run()
        elapsed = time.monotonic() - t0
        assert rc == 0, agent.launch_history
        # the watchdog (10s deadline), not the 600s hang, nor the 90s
        # commit timeout, nor a heartbeat guess, ended incarnation 1
        assert agent.comm_hang_count == 1, agent.launch_history
        assert agent.launch_history[0]["comm_hang"]
        assert agent.teardown_count == 1  # rank 0 was torn down promptly
        assert agent.restart_count == 0
        assert elapsed < 600, "hang was waited out instead of aborted"

        ckpt = tmp_path / "ckpt_hang"
        # the torn step-8 tag of incarnation 1 was quarantined, never
        # resolved; incarnation 2's final save re-created it complete
        assert any(n.startswith("global_step8.corrupt")
                   for n in os.listdir(ckpt))
        assert verify_tree(str(ckpt / "global_step8"))[0]
        assert pod_complete(str(ckpt / "global_step8"))[0]

        head1, inc1 = self._losses(tmp_path, "hang", rank=0, attempt=0)
        head2, inc2 = self._losses(tmp_path, "hang", rank=0, attempt=1)
        assert head1["resumed"] is None
        assert head2["resumed"] == "global_step4"   # newest POD-COMPLETE
        assert head2["start_step"] == 4
        # bit-identical: pre-fault steps AND the resumed tail
        assert {s: inc1[s] for s in (1, 2, 3, 4)} == \
            {s: ref[s] for s in (1, 2, 3, 4)}
        assert inc2 == {s: ref[s] for s in (5, 6, 7, 8)}

        # pod report over incarnation 1's streams names the culprit
        pod = _load_pod_module()
        report = pod.pod_report_from_paths(
            [str(tmp_path / "tele_hang" / "att0")])
        assert report is not None and report.comm_hang is not None
        h = report.comm_hang
        assert h["step"] == 6
        assert h["culprit_rank"] == 1, h
        assert h["culprit_reason"] in ("never-completed", "never-arrived")
        assert 1 in h.get("detected_by_ranks", []), h

        # offline verdicts agree: every surviving tag is pod-complete
        check_ckpt = _load_check_ckpt()
        assert check_ckpt.main([str(ckpt)]) == 0
