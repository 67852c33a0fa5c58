"""Request-time attribution tests (``monitor/reqtrace.py`` + the stamping
hooks in ``inference/v2/serving.py`` / ``fleet/router.py``).

The join/attribution core is stdlib-only, so most of this file drives it on
synthetic journal records (torn tails, generation respawns, cross-replica
failover replays) with hand-computable interval partitions. One class
drives a REAL session on the CPU sim and checks the reconciliation
contract end to end: stage self-times must sum to the journal-observed
enqueue→close wall time within 5%. The CLI class re-proves the login-node
contract: ``tools/trace_report.py --requests`` renders with jax import
blocked.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from deepspeedsyclsupport_tpu.analysis import codelint
from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.inference.v2.supervisor import (
    journal_path)
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.monitor import reqtrace
from deepspeedsyclsupport_tpu.monitor.telemetry import (
    export_metrics_textfile, prometheus_name)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _r(name, t, **data):
    """One journal/trace record in the shape every stream shares."""
    return {"name": name, "t": float(t), "data": data}


def _write_stream(path, records, torn_tail=None):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
        if torn_tail is not None:
            f.write(torn_tail)  # no newline: crash mid-write
    return path


def _closed_request(uid, t0, queue_s=0.4, prefill_s=0.6, itl_s=0.5,
                    tokens=3, sla=None, cached=None):
    """A full lifecycle: admit → activate → first emit → decodes → close.
    The interval partition is exact by construction, so the expected
    per-stage seconds are the arguments themselves."""
    recs = [_r("serve/admit", t0, uid=uid, tokens=[1, 2, 3],
               tenant="default", ttft_sla_s=sla)]
    t = t0 + queue_s
    act = {"uid": uid, "stage": "queue_wait", "dur": queue_s}
    if cached is not None:
        act["cached_prefix_len"] = cached
    recs.append({"name": "serve/stage", "t": t, "data": act})
    t += prefill_s
    recs.append(_r("serve/emit", t, uid=uid, n=1))
    for _ in range(tokens - 1):
        t += itl_s
        recs.append(_r("serve/emit", t, uid=uid, n=1))
    t += 0.2
    recs.append(_r("serve/close", t, uid=uid, reason="done"))
    return recs, t


# ==================================================================
# stage registry
# ==================================================================
class TestStageRegistry:
    def test_declared_names_pass(self):
        for name in reqtrace.SERVE_STAGES:
            assert reqtrace.check_stage(name) == name
        for name in reqtrace.FLEET_STAGES:
            assert reqtrace.check_stage(name, fleet=True) == name

    def test_typo_raises_with_declared_list(self):
        with pytest.raises(ValueError, match="undeclared serve stage"):
            reqtrace.check_stage("queue_wat")
        with pytest.raises(ValueError, match="undeclared fleet stage"):
            reqtrace.check_stage("queue_wait", fleet=True)

    def test_histogram_stages_are_declared(self):
        assert set(reqtrace.STAGE_HISTOGRAMS) <= set(reqtrace.SERVE_STAGES)


# ==================================================================
# join: synthetic streams
# ==================================================================
class TestJoinSynthetic:
    def test_partition_telescopes_exactly(self):
        recs, _ = _closed_request(1, 100.0, queue_s=0.4, prefill_s=0.6,
                                  itl_s=0.5, tokens=3, cached=2)
        tr = reqtrace.join_traces([("0", "0", recs)])[1]
        assert tr["ttft_s"] == pytest.approx(1.0)
        assert tr["stages"]["queue_wait"] == pytest.approx(0.4)
        assert tr["stages"]["prefill"] == pytest.approx(0.6)
        assert tr["stages"]["decode"] == pytest.approx(1.0)
        assert tr["stages"]["finalize"] == pytest.approx(0.2)
        # a consecutive partition reconciles to 1.0 by construction
        assert tr["reconciled_frac"] == pytest.approx(1.0)
        assert tr["unattributed_s"] == pytest.approx(0.0)
        assert tr["tokens"] == 3 and tr["closes"] == 1
        assert tr["outcome"] == "closed"
        assert tr["cached_prefix_len"] == 2

    def test_route_stamp_after_admit_keeps_attribution(self):
        # an in-process router stamps fleet/route AFTER the replica's
        # serve/admit (replica.submit returns before the router records
        # the route); the late route edge is metadata and must not break
        # the admit→activate→emit chain into unattributed time
        recs, _ = _closed_request(7, 100.0, queue_s=0.4, prefill_s=0.6,
                                  itl_s=0.5, tokens=3)
        router = [_r("fleet/stage", 100.0, uid=7, stage="edge_gate",
                     verdict="admit"),
                  _r("fleet/stage", 100.0001, uid=7, stage="placement",
                     replica="0"),
                  _r("fleet/route", 100.0002, uid=7, replica="0")]
        tr = reqtrace.join_traces([("0", "", recs)],
                                  router_records=router)[7]
        assert tr["t_route"] == pytest.approx(100.0002)
        assert tr["replica_path"] == ["0"]
        assert tr["stages"]["queue_wait"] == pytest.approx(0.4)
        assert tr["stages"]["prefill"] == pytest.approx(0.6)
        assert tr["reconciled_frac"] == pytest.approx(1.0)
        assert tr["unattributed_s"] == pytest.approx(0.0)

    def test_decode_round_fanout_and_spool_wait(self):
        recs, _ = _closed_request(1, 10.0)
        # an older journal's record (its stage, a ``mode``) is a round too
        recs.append(_r("serve/stage", 10.5, uid=-1, stage="decode_round",
                       mode="fused", uids=[1]))
        recs.append(_r("serve/stage", 10.6, uid=-1, stage="round",
                       uids=[1]))
        recs.append(_r("serve/stage", 10.0, uid=1, stage="spool_wait",
                       dur=0.03))
        tr = reqtrace.join_traces([("0", "0", recs)])[1]
        assert tr["rounds"] == 2
        assert tr["spool_wait_s"] == pytest.approx(0.03)

    def test_torn_tail_salvaged(self, tmp_path):
        jdir = tmp_path / "journal"
        recs, _ = _closed_request(7, 50.0)
        _write_stream(str(jdir / "journal_rank0.att0.jsonl"), recs,
                      torn_tail='{"name": "serve/adm')
        traces = reqtrace.join_root(str(jdir))
        assert set(traces) == {7}
        assert traces[7]["closes"] == 1
        assert traces[7]["reconciled_frac"] == pytest.approx(1.0)

    def test_generation_respawn_spans_attempts(self, tmp_path, monkeypatch):
        """A pool respawn bumps DSTPU_FLEET_GEN: the dead generation's
        journal carries admit+emit with no close, the survivor generation
        re-admits (replayed) and closes. The join fuses both files into one
        trace with exactly one close and a named replay interval."""
        jdir = str(tmp_path / "journal")
        monkeypatch.setenv("DSTPU_ELASTIC_ATTEMPT", "0")
        monkeypatch.setenv("DSTPU_FLEET_GEN", "1")
        p1 = journal_path(jdir)
        assert p1.endswith("journal_rank0.att1.0.jsonl")
        _write_stream(p1, [
            _r("serve/admit", 10.0, uid=5, tokens=[1, 2, 3]),
            _r("serve/stage", 10.1, uid=5, stage="queue_wait", dur=0.1),
            _r("serve/emit", 10.5, uid=5, n=1),
        ])  # killed here — no close
        monkeypatch.setenv("DSTPU_FLEET_GEN", "2")
        p2 = journal_path(jdir)
        _write_stream(p2, [
            _r("serve/admit", 12.0, uid=5, replayed=True, watermark=1,
               tokens=[1, 2, 3]),
            _r("serve/stage", 12.1, uid=5, stage="requeue_wait", dur=0.1),
            _r("serve/emit", 12.4, uid=5, n=1),
            _r("serve/close", 12.6, uid=5, reason="done"),
        ])
        os.utime(p1, (1000, 1000))
        os.utime(p2, (2000, 2000))
        assert reqtrace.file_attempt(p1) == "1.0"
        assert reqtrace.file_attempt(p2) == "2.0"
        traces = reqtrace.join_root(jdir)
        tr = traces[5]
        assert tr["closes"] == 1  # exactly-once close across generations
        assert [s["attempt"] for s in tr["segments"]] == ["1.0", "2.0"]
        assert tr["segments"][1]["replayed"] is True
        # dead-emit → survivor-admit gap is named, not unattributed
        assert tr["stages"]["replay"] == pytest.approx(1.5)
        assert tr["ttft_s"] == pytest.approx(0.5)  # first segment's TTFT
        assert tr["reconciled_frac"] == pytest.approx(1.0)

    def test_failover_replay_across_replicas(self):
        """Dead replica's segment + survivor's replay segment + the router
        stream fuse into one trace: one close, failover counted, transport
        and replay intervals named."""
        dead = [
            _r("serve/admit", 10.0, uid=3, tokens=[1, 2]),
            _r("serve/stage", 10.2, uid=3, stage="queue_wait", dur=0.2),
            _r("serve/emit", 10.6, uid=3, n=1),
        ]
        survivor = [
            _r("serve/admit", 13.0, uid=3, replayed=True, watermark=1),
            _r("serve/stage", 13.1, uid=3, stage="requeue_wait", dur=0.1),
            _r("serve/emit", 13.4, uid=3, n=1),
            _r("serve/close", 13.6, uid=3, reason="done"),
        ]
        router = [
            _r("fleet/stage", 9.8, uid=3, stage="edge_gate",
               verdict="admit", n_prompt=2),
            _r("fleet/stage", 9.9, uid=3, stage="placement", replica="0",
               sticky=False),
            _r("fleet/route", 9.9, uid=3, replica="0"),
            _r("fleet/failover", 12.9, uid=3, outcome="replayed",
               replica="1"),
            _r("fleet/stage", 12.9, uid=3, stage="replay_segment",
               replica="1", watermark=1),
        ]
        traces = reqtrace.join_traces(
            [("0", "0", dead), ("1", "0", survivor)], router_records=router)
        tr = traces[3]
        assert tr["closes"] == 1
        assert tr["replays"] == 1
        assert tr["replica_path"] == ["0", "1"]
        assert "replay" in tr["stages"]
        assert tr["verdicts"][:2] == ["admit", "routed"]
        att = reqtrace.attribution(traces)
        assert att["failover_spans"] == 1
        assert att["multi_close"] == 0
        assert att["closed"] == 1

    def test_edge_shed_and_since_filter(self):
        router = [_r("fleet/shed", 10.0, uid=9, reason="edge_depth")]
        recs, _ = _closed_request(1, 1000.0)
        traces = reqtrace.join_traces([("0", "0", recs)],
                                      router_records=router)
        assert traces[9]["outcome"] == "edge_shed"
        assert traces[9]["close_reason"] == "edge_shed:edge_depth"
        late = reqtrace.join_traces([("0", "0", recs)],
                                    router_records=router, since=500.0)
        assert set(late) == {1}  # the t=10 shed predates the window

    def test_attribution_population(self):
        """20 requests with spread TTFTs: quantile families, tail
        attribution, SLO burn windows and worst-N all populate, and every
        request reconciles within the 5% contract."""
        recs = []
        for i in range(20):
            r, _ = _closed_request(
                i + 1, 100.0 + 2.0 * i, queue_s=0.1 + 0.05 * (i % 5),
                prefill_s=0.3 + (0.8 if i >= 18 else 0.0),
                itl_s=0.2, tokens=3, sla=0.5)
            recs.extend(r)
        att = reqtrace.attribution(
            reqtrace.join_traces([("0", "0", recs)]),
            worst_n=4, slo_window_s=10.0, slo_budget=0.05)
        assert att["requests"] == att["closed"] == 20
        assert att["reconciliation"]["within_5pct_frac"] == pytest.approx(1.0)
        assert att["reconciliation"]["min_frac"] >= 0.95
        for stage in ("queue_wait", "prefill"):
            qs = att["ttft_by_stage"][stage]
            assert qs["p50"] is not None and qs["p95"] >= qs["p50"]
        assert att["dominant_ttft_stage"] in reqtrace.SERVE_STAGES
        assert att["itl_by_stage"]["decode"]["p50"] == pytest.approx(0.2)
        # the two slow requests carry +0.8s of prefill: the tail names it
        assert att["tail"] is not None
        assert att["tail"]["dominant_stage"] == "prefill"
        assert att["tail"]["by_stage"]["prefill"]["growth_s"] > 0.5
        assert att["slo_burn"]["windows"], "SLA'd requests must yield burn"
        assert att["slo_burn"]["max_burn"] is not None
        assert len(att["worst"]) == 4
        ttfts = [w["ttft_s"] for w in att["worst"]]
        assert ttfts == sorted(ttfts, reverse=True)
        assert att["worst"][0]["stages"]["prefill"] == pytest.approx(1.1)


# ==================================================================
# live session: the reconciliation contract end to end
# ==================================================================
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


class TestLiveSessionJoin:
    def test_session_drive_reconciles_and_surfaces(self, tiny, tmp_path):
        model, params = tiny
        eng = _v2(model, params)
        sess = ServingSession(eng, ServingPolicyConfig())
        try:
            for uid, prompt in [(1, [1, 2, 3]), (2, [4, 5, 6]),
                                (3, [7, 8, 9])]:
                assert sess.submit(uid, prompt, 6, ttft_sla_s=30.0) \
                    == "admitted"
            steps = 0
            while not sess.idle:
                sess.step()
                steps += 1
                assert steps < 400, "session did not converge"
            traces = reqtrace.join_traces([("0", "", sess.drain_trace())])
            att = reqtrace.attribution(traces)
            assert att["closed"] == 3 and att["multi_close"] == 0
            # the acceptance contract: ≥95% of requests reconcile within 5%
            assert att["reconciliation"]["within_5pct_frac"] >= 0.95
            assert att["dominant_ttft_stage"] is not None
            # three requests of six tokens, a round a token each
            assert att["decode_rounds"] == 18
            for w in att["worst"]:
                assert w["stages"], "worst waterfalls must carry stages"
            # queue-wait histogram + SLO gauges ride summary_events
            # (strict-registry validated inside summary_events itself)
            names = {e[0] for e in sess.summary_events(step=0)}
            assert "Serve/slo.burn_rate" in names
            assert "Serve/slo.ttft_miss_frac" in names
            assert any(n.startswith("Serve/queue_wait_s/") for n in names)
            # prometheus textfile export from the serving registry
            prom = str(tmp_path / "metrics_rank0.prom")
            assert sess.export_metrics(prom) == prom
            text = open(prom).read()
            assert prometheus_name("Serve/queue_wait_s") + "_count" in text
        finally:
            sess.close()


# ==================================================================
# prometheus textfile exporter
# ==================================================================
class TestTextfileExport:
    SNAP = {"counters": {"Serve/admitted": 3},
            "gauges": {"Serve/slo.burn_rate": 0.5},
            "histograms": {"Serve/queue_wait_s": {
                "buckets": [0.1, 1.0], "counts": [2, 1, 1],
                "sum": 1.9, "count": 4}}}

    def test_atomic_cumulative_export(self, tmp_path):
        path = str(tmp_path / "metrics" / "metrics_rank0.prom")
        out = export_metrics_textfile(path, self.SNAP,
                                      labels={"role": "replica"},
                                      extra_counters={"fleet_routed": 7})
        assert out == path and os.path.exists(path)
        # atomic-rename contract: no torn .tmp<pid> survives the write
        assert [f for f in os.listdir(os.path.dirname(path))
                if ".tmp" in f] == []
        text = open(path).read()
        adm = prometheus_name("Serve/admitted")
        qw = prometheus_name("Serve/queue_wait_s")
        assert f'# TYPE {adm} counter' in text
        assert adm + '{role="replica"} 3' in text
        assert prometheus_name("fleet_routed") + '{role="replica"} 7' in text
        assert (prometheus_name("Serve/slo.burn_rate")
                + '{role="replica"} 0.5') in text
        # cumulative buckets: 2, 3, then +Inf picks up the overflow count
        assert 'le="0.1"} 2' in text
        assert 'le="1.0"} 3' in text
        assert 'le="+Inf"} 4' in text
        assert qw + '_count{role="replica"} 4' in text


# ==================================================================
# offline CLI: the login-node contract
# ==================================================================
def _jax_blocked_env(tmp_path):
    blocker = tmp_path / "nojax"
    blocker.mkdir(exist_ok=True)
    (blocker / "jax.py").write_text(
        "raise ImportError('jax blocked: trace_report must be stdlib-only')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(blocker)
    return env


class TestRequestsReportCLI:
    def _mk_root(self, tmp_path):
        jdir = tmp_path / "root" / "replica0" / "journal"
        recs = []
        for i in range(6):
            r, _ = _closed_request(i + 1, 100.0 + i, sla=0.5)
            recs.extend(r)
        _write_stream(str(jdir / "journal_rank0.att0.jsonl"), recs,
                      torn_tail='{"torn')
        return str(tmp_path / "root")

    def test_renders_with_jax_import_blocked(self, tmp_path):
        root = self._mk_root(tmp_path)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             "--requests", root],
            env=_jax_blocked_env(tmp_path),
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "request-time attribution" in out.stdout
        assert "TTFT by stage" in out.stdout
        assert "reconciliation" in out.stdout
        assert "dominant" in out.stdout

    def test_empty_root_exits_2(self, tmp_path):
        empty = tmp_path / "empty"
        empty.mkdir()
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             "--requests", str(empty)],
            env=_jax_blocked_env(tmp_path),
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 2


# ==================================================================
# dslint: undeclared-stage-name
# ==================================================================
def _lint_file(tmp_path, relpath, source, rules):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    return codelint.lint_paths(str(tmp_path), relpaths=[relpath],
                               rules=rules)


class TestUndeclaredStageNameRule:
    RULE = [codelint.UndeclaredStageName()]

    def test_typo_in_stage_call_flagged(self, tmp_path):
        src = ("class S:\n"
               "    def f(self, uid, t):\n"
               "        self._stage(uid, 'queue_wat', t)\n")
        vs = _lint_file(tmp_path, "inference/v2/x.py", src, self.RULE)
        assert any(v.rule == "undeclared-stage-name" for v in vs)

    def test_typo_in_record_payload_flagged(self, tmp_path):
        src = "REC = {'uid': 1, 'stage': 'plcement'}\n"
        vs = _lint_file(tmp_path, "inference/v2/x.py", src, self.RULE)
        assert any(v.rule == "undeclared-stage-name" for v in vs)

    def test_declared_stages_clean(self, tmp_path):
        src = ("class S:\n"
               "    def f(self, uid, t, queued):\n"
               "        self._stage(uid, 'requeue_wait' if queued else\n"
               "                    'queue_wait', t)\n"
               "        self.note_stage(uid, 'spool_wait', dur=0.1)\n")
        assert _lint_file(tmp_path, "inference/v2/x.py", src,
                          self.RULE) == []

    def test_registered_in_all_rules(self):
        assert "undeclared-stage-name" in {r.name for r in
                                           codelint.ALL_RULES}
