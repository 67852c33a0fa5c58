"""MFU ledger: roofline partition + trace join + report tools + ring A/B.

Covers the step-time attribution stack end to end:

* ``monitor/mfu.py`` units — HLO opmap building (named_scope metadata →
  region, collective override), Chrome-trace parsing with gzip/JSON
  truncation salvage, and the wall-exact region measurement (nested-event
  self-time, cross-thread even split, orphan accounting).
* ``analysis/roofline.py`` — per-region jaxpr costs through grad+scan,
  bound-by verdicts against a device spec, census-byte injection.
* the engine e2e: ``telemetry.mfu`` clean-step window capture,
  ``Engine.mfu_ledger()``, the ledger↔goodput reconciliation contract
  (region sum within 5% of the measured clean step; the window step lands
  in goodput's productive bucket with accounting ≥99%), strict ``MFU/*``
  event registration.
* ring-attention ``attn_impl`` wiring: flash-inner parity against the
  inline path and the two-arm A/B under the ledger.
* the offline tools: ``tools/mfu_report.py`` on the checked-in miniature
  fixture with jax import BLOCKED (the login-node contract) and truncated
  trace salvage.
"""
import gzip
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "mfu")

from deepspeedsyclsupport_tpu.monitor import mfu  # noqa: E402


# ===================================================================
# opmap (HLO metadata -> region)
# ===================================================================
_HLO = """\
HloModule jit_train

%fused_computation.3 {
  %p0 = f32[512]{0} parameter(0)
  ROOT %exp.1 = f32[512]{0} exponential(f32[512]{0} %p0), metadata={op_name="jit(f)/jvp(mfu.attn)/exp"}
}

ENTRY %main {
  %Arg_0.1 = f32[512,512]{1,0} parameter(0)
  %dot.12 = f32[512,512]{1,0} dot(f32[512,512]{1,0} %Arg_0.1, f32[512,512]{1,0} %Arg_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jvp(mfu.attn)/ij,jk->ik/dot_general" source_file="x.py"}
  %dot.33 = f32[512,512]{1,0} dot(f32[512,512]{1,0} %dot.12, f32[512,512]{1,0} %Arg_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(f)/transpose(jvp(mfu.mlp))/dot_general"}
  %subtract_exponential_fusion = f32[512,512]{1,0} fusion(f32[512,512]{1,0} %dot.12), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/jvp(mfu.attn)/exp"}
  %all-gather.7 = f32[512,512]{1,0} all-gather(f32[512,512]{1,0} %dot.33), dimensions={0}, metadata={op_name="jit(f)/jvp(mfu.mlp)/gather"}
  %norm.2 = f32[512,512]{1,0} multiply(f32[512,512]{1,0} %dot.12, f32[512,512]{1,0} %dot.33), metadata={op_name="jit(f)/rms_norm/mul"}
  ROOT %tuple.9 = (f32[512,512]{1,0}) tuple(f32[512,512]{1,0} %norm.2)
}
"""


class TestOpmap:
    def test_regions_from_metadata_forward_and_backward(self):
        om = mfu.build_opmap(_HLO)
        assert om["dot.12"]["region"] == "attn"        # jvp(mfu.attn)
        assert om["dot.33"]["region"] == "mlp"         # transpose(jvp(...))
        assert om["subtract_exponential_fusion"]["region"] == "attn"
        assert om["subtract_exponential_fusion"]["category"] == "fusion"
        assert om["dot.12"]["category"] == "dot"

    def test_collective_opcode_overrides_scope(self):
        om = mfu.build_opmap(_HLO)
        # scoped mfu.mlp but an all-gather IS collective traffic
        assert om["all-gather.7"]["region"] == "collective"
        assert om["all-gather.7"]["category"] == "collective"

    def test_unscoped_and_plumbing(self):
        om = mfu.build_opmap(_HLO)
        assert om["norm.2"]["region"] == "other"       # no mfu.* scope
        assert "Arg_0.1" not in om                     # parameters skipped
        assert "tuple.9" not in om
        # nested-computation instructions are mapped too (trace events are
        # named by instruction regardless of computation)
        assert om["exp.1"]["region"] == "attn"

    def test_tuple_result_instructions_match(self):
        """``while`` loops (the scan trunk) and COMBINED variadic
        all-reduces (the main grad-sync traffic) have tuple result types
        with internal spaces — missing them orphans exactly the time the
        instrument exists to name."""
        hlo = (
            '  %while.11 = (f32[8]{0}, s32[]) while((f32[8]{0}, s32[]) '
            '%tuple.3), condition=%cond.1, body=%body.2, '
            'metadata={op_name="jit(f)/scan/while"}\n'
            '  %all-reduce.5 = (f32[4]{0}, f32[8]{0}) all-reduce('
            'f32[4]{0} %a, f32[8]{0} %b), replica_groups={}, '
            'to_apply=%add.9\n')
        om = mfu.build_opmap(hlo)
        assert om["while.11"]["category"] == "control"
        assert om["while.11"]["region"] == "other"
        assert om["all-reduce.5"]["region"] == "collective"
        # TPU layouts put NESTED parens inside the tuple (tiling
        # annotations) — the exact spelling real-TPU compiled.as_text()
        # prints for a combined grad-sync all-reduce
        tpu = ('  %all-reduce.1 = (bf16[4096]{0:T(1024)}, '
               'bf16[128]{0:T(128)}) all-reduce(bf16[4096]{0:T(1024)} '
               '%a, bf16[128]{0:T(128)} %b), replica_groups={}, '
               'to_apply=%add.2\n')
        assert mfu.build_opmap(tpu)["all-reduce.1"]["region"] == \
            "collective"

    def test_region_of_last_match_wins_and_unknown_is_none(self):
        assert mfu.region_of("jit(f)/mfu.attn/mfu.mlp/dot") == "mlp"
        assert mfu.region_of("jit(f)/mfu.bogus/dot") is None
        assert mfu.region_of("jit(f)/plain/dot") is None

    def test_region_scope_rejects_undeclared(self):
        with pytest.raises(ValueError, match="undeclared MFU region"):
            mfu.region_scope("attnn")

    @pytest.mark.parametrize("path,region,want", [
        ("jit(f)/jvp(mfu.mlp)/dot_general", "mlp", "fwd"),
        ("jit(f)/jvp()/while/body/closed_call/mfu.attn/exp", "attn", "fwd"),
        ("jit(f)/transpose(jvp(mfu.head))/dot_general", "head", "bwd"),
        ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "mfu.mlp/dot_general", "mlp", "bwd"),
        ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/mfu.mlp/tanh", "mlp", "recompute"),
        # unscoped work inside the differentiated function keeps its pass
        ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/rms_norm/mul", "other", "recompute"),
        ("jit(f)/transpose(jvp())/mul", "other", "bwd"),
        # outside it there is none: the update, the loss scale, plumbing
        ("jit(f)/mfu.optimizer/sub", "optimizer", None),
        ("jit(f)/convert_element_type", "other", None),
        ("", "other", None),
        # a component that merely CONTAINS the words is not the component
        ("jit(f)/jvp(mfu.mlp)/my_rematted_computation_probe/x", "mlp",
         "fwd"),
    ])
    def test_pass_of_reads_the_path_components(self, path, region, want):
        assert mfu.pass_of(path, region) == want

    def test_opmap_carries_pass_and_path_with_or_without_percent(self):
        om = mfu.build_opmap(_HLO)
        assert om["dot.12"]["pass"] == "fwd"
        assert om["dot.33"]["pass"] == "bwd"
        assert om["norm.2"]["pass"] is None
        assert om["all-gather.7"]["region"] == "collective"
        assert om["dot.33"]["op_name"] == \
            "jit(f)/transpose(jvp(mfu.mlp))/dot_general"
        # a dump printed without the sigil (print_percent off; the text
        # benchmark/scopes.py also accepts) maps the same
        assert mfu.build_opmap(_HLO.replace("%", "")) == om


# ===================================================================
# trace parsing + salvage
# ===================================================================
def _trace_bytes(events):
    return json.dumps({"displayTimeUnit": "ns", "metadata": {},
                       "traceEvents": events}).encode()


class TestTraceParse:
    EVENTS = [{"ph": "X", "pid": 1, "tid": 2, "ts": float(i * 10),
               "dur": 5.0, "name": f"dot.{i}",
               "args": {"hlo_op": f"dot.{i}"}} for i in range(8)]

    def test_plain_json_and_gz(self, tmp_path):
        raw = _trace_bytes(self.EVENTS)
        p1 = tmp_path / "a.trace.json"
        p1.write_bytes(raw)
        p2 = tmp_path / "b.trace.json.gz"
        p2.write_bytes(gzip.compress(raw))
        for p in (p1, p2):
            events, meta = mfu.parse_trace(str(p))
            assert len(events) == 8 and not meta["truncated"]

    def test_torn_gzip_salvages(self, tmp_path):
        raw = gzip.compress(_trace_bytes(self.EVENTS))
        p = tmp_path / "torn.trace.json.gz"
        p.write_bytes(raw[:int(len(raw) * 0.6)])
        events, meta = mfu.parse_trace(str(p))
        assert meta["truncated"]
        # whatever whole events survived the torn stream are kept
        assert 0 <= len(events) < 8

    def test_torn_json_salvages_complete_events(self, tmp_path):
        raw = _trace_bytes(self.EVENTS)
        cut = raw[:raw.rfind(b'{"ph"')] + b'{"ph": "X", "ts": 1'
        p = tmp_path / "torn.trace.json"
        p.write_bytes(cut)
        events, meta = mfu.parse_trace(str(p))
        assert meta["truncated"]
        assert len(events) == 7  # every COMPLETE event kept

    def test_find_trace_walks_profiler_layout(self, tmp_path):
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        (d / "host.trace.json.gz").write_bytes(
            gzip.compress(_trace_bytes(self.EVENTS)))
        assert mfu.find_trace(str(tmp_path)).endswith("host.trace.json.gz")
        assert mfu.find_trace(str(tmp_path / "nope")) is None


# ===================================================================
# region measurement (self-time + even split + orphans)
# ===================================================================
class TestMeasureRegions:
    OPMAP = {
        "while.10": {"region": "other", "category": "control",
                     "opcode": "while"},
        "dot.1": {"region": "attn", "category": "dot", "opcode": "dot"},
        "fus.2": {"region": "mlp", "category": "fusion", "opcode": "fusion"},
    }

    @staticmethod
    def _ev(name, ts, dur, tid=7):
        return {"ph": "X", "pid": 1, "tid": tid, "ts": float(ts),
                "dur": float(dur), "name": name,
                "args": {"hlo_op": name}}

    def test_nested_events_self_time(self):
        # while [0,100) contains dot [10,40) and fus [40,80): the while
        # event's own region gets only its UNCOVERED 30us — a plain sum
        # would bill 170us of work against 100us of wall
        events = [self._ev("while.10", 0, 100), self._ev("dot.1", 10, 30),
                  self._ev("fus.2", 40, 40)]
        m = mfu.measure_regions(events, self.OPMAP)
        assert m["regions"]["attn"] == pytest.approx(30e-6)
        assert m["regions"]["mlp"] == pytest.approx(40e-6)
        assert m["regions"]["other"] == pytest.approx(30e-6)
        assert m["device_busy_s"] == pytest.approx(100e-6)
        assert sum(m["regions"].values()) == pytest.approx(
            m["mapped_union_s"])

    def test_concurrent_threads_split_evenly(self):
        # two threads fully overlapped [0,10): each instant splits 50/50
        events = [self._ev("dot.1", 0, 10, tid=1),
                  self._ev("fus.2", 0, 10, tid=2)]
        m = mfu.measure_regions(events, self.OPMAP)
        assert m["regions"]["attn"] == pytest.approx(5e-6)
        assert m["regions"]["mlp"] == pytest.approx(5e-6)
        assert m["device_busy_s"] == pytest.approx(10e-6)

    def test_orphan_ops_counted_but_unattributed(self):
        events = [self._ev("dot.1", 0, 10),
                  self._ev("copy.unknown", 20, 5)]
        m = mfu.measure_regions(events, self.OPMAP)
        assert m["orphan_s"] == pytest.approx(5e-6)
        assert m["n_unmapped"] == 1
        assert m["device_busy_s"] == pytest.approx(15e-6)
        # host-runtime events (no hlo_op arg, not in opmap) are ignored
        events.append({"ph": "X", "pid": 1, "tid": 9, "ts": 0.0,
                       "dur": 99.0, "name": "PjitFunction(f)"})
        m2 = mfu.measure_regions(events, self.OPMAP)
        assert m2["device_busy_s"] == pytest.approx(15e-6)

    def test_steps_normalization(self):
        events = [self._ev("dot.1", 0, 10), self._ev("dot.1", 100, 10)]
        m = mfu.measure_regions(events, self.OPMAP, steps=2)
        assert m["regions"]["attn"] == pytest.approx(10e-6)

    def test_a_tpu_op_the_map_lacks_is_an_orphan_not_nothing(self):
        """A v5e's ``XLA Ops`` events carry ``hlo_category`` and
        ``long_name``, no ``hlo_op`` (read off its Chrome trace, PR 35):
        110 ``async-start`` instructions a parser bug kept out of the opmap
        went uncounted there, ``n_unmapped`` 0, until the arg was read."""
        tpu = {"ph": "X", "pid": 3, "tid": 3, "ts": 20.0, "dur": 5.0,
               "name": "slice-start.48",
               "args": {"hlo_category": "async-start",
                        "long_name": "%slice-start.48 = ((f32[2]), f32[1], "
                                     "s32[]) async-start(f32[2] %p)"}}
        module = {"ph": "X", "pid": 3, "tid": 2, "ts": 0.0, "dur": 30.0,
                  "name": "jit_train_batch_fn(6095717265277617000)",
                  "args": {"run_id": "22"}}
        m = mfu.measure_regions([self._ev("dot.1", 0, 10), tpu, module],
                                self.OPMAP)
        assert (m["n_mapped"], m["n_unmapped"]) == (1, 1)
        assert m["orphan_s"] == pytest.approx(5e-6)
        # ... and the line that instruction has on a TPU now parses: a
        # tuple in a tuple, layouts with parens of their own
        line = ('  %slice-start.48 = ((f32[2,4096,1024]{2,1,0:T(8,128)}), '
                'f32[1,4096,1024]{2,1,0:T(8,128)S(1)}, s32[]{:S(2)}) '
                'async-start(f32[2,4096,1024]{2,1,0:T(8,128)} %p), '
                'calls=%async_computation.3\n'
                '  %copy-start.5 = (bf16[4,2048]{1,0:T(8,128)(2,1)S(1)}, '
                'bf16[4,2048]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start('
                'bf16[4,2048]{1,0:T(8,128)(2,1)} %q), metadata={op_name='
                '"jit(f)/transpose(jvp())/checkpoint/mfu.mlp/mul"}\n')
        om = mfu.build_opmap(line)
        assert om["slice-start.48"]["opcode"] == "async-start"
        assert (om["copy-start.5"]["region"], om["copy-start.5"]["pass"]) \
            == ("mlp", "bwd")

    def test_region_by_pass_rows_resum_to_the_regions(self):
        opmap = {
            "dot.1": {"region": "attn", "pass": "fwd", "category": "dot"},
            "dot.2": {"region": "attn", "pass": "bwd", "category": "dot"},
            "fus.3": {"region": "attn", "pass": "recompute",
                      "category": "fusion"},
            "fus.4": {"region": "optimizer", "pass": None,
                      "category": "fusion"},
            # an opmap persisted before the pass existed still joins
            "fus.5": {"region": "other", "category": "fusion"},
        }
        events = [self._ev("dot.1", 0, 10), self._ev("dot.2", 10, 20),
                  self._ev("fus.3", 30, 5), self._ev("fus.4", 40, 8),
                  self._ev("fus.5", 50, 2)]
        m = mfu.measure_regions(events, opmap)
        assert m["passes"] == {
            "attn": {"fwd": pytest.approx(10e-6), "bwd": pytest.approx(20e-6),
                     "recompute": pytest.approx(5e-6)},
            "optimizer": {"-": pytest.approx(8e-6)},
            "other": {"-": pytest.approx(2e-6)}}
        for region, row in m["passes"].items():
            assert sum(row.values()) == pytest.approx(m["regions"][region])
        led = mfu.ledger(None, m, step_s=50e-6)
        assert led["passes"] == m["passes"]
        assert (led["n_mapped"], led["n_unmapped"]) == (5, 0)
        text = mfu.render_ledger(led)
        assert "region x pass" in text
        row = next(ln for ln in text.splitlines()
                   if ln.split()[:1] == ["attn"] and "us" in ln
                   and "%" not in ln)
        assert row.split() == ["attn", "10us", "20us", "5us", "-"]


# ===================================================================
# ledger math + events
# ===================================================================
class TestLedgerMath:
    ROOFLINE = {
        "device": "spec-x",
        "spec": {"name": "spec-x", "peak_flops": 1e9, "hbm_gbps": 1.0,
                 "ici_gbps": 1.0},
        "regions": {"attn": {"flops": 4e4, "hbm_bytes": 0, "comm_bytes": 0,
                             "achievable_s": 4e-5, "bound_by": "compute"}},
        "total_flops": 4e4, "total_achievable_s": 4e-5,
    }

    def _measured(self):
        return {"regions": {"attn": 60e-6}, "categories": {"dot": 60e-6},
                "device_busy_s": 60e-6, "mapped_union_s": 60e-6,
                "orphan_s": 0.0, "n_mapped": 3, "n_unmapped": 0, "steps": 1}

    def test_waterfall_and_mfu(self):
        led = mfu.ledger(self.ROOFLINE, self._measured(), step_s=80e-6)
        assert not mfu.validate_ledger(led)
        levels = [w["level"] for w in led["waterfall"]]
        assert levels == ["hardware_peak", "roofline_achievable",
                          "measured"]
        assert led["waterfall"][0]["s"] == pytest.approx(4e-5)
        assert led["achieved_mfu"] == pytest.approx(4e4 / (80e-6 * 1e9))
        assert led["roofline_mfu"] == pytest.approx(1.0)
        assert led["regions"]["host"]["measured_s"] == pytest.approx(20e-6)
        assert led["regions"]["attn"]["headroom"] == pytest.approx(1.5)
        rec = led["reconciliation"]
        assert rec["frac"] == pytest.approx(1.0)
        assert led["top_sinks"][0] == "attn"

    def test_measured_only_without_roofline(self):
        led = mfu.ledger(None, self._measured(), step_s=80e-6)
        assert led["achieved_mfu"] is None and led["waterfall"] == []
        assert "MFU ledger" in mfu.render_ledger(led)

    def test_ledger_events_strict_registered(self, monkeypatch):
        from deepspeedsyclsupport_tpu.monitor.telemetry import check_events

        monkeypatch.setenv("DSTPU_STRICT_EVENTS", "1")
        led = mfu.ledger(self.ROOFLINE, self._measured(), step_s=80e-6)
        ev = mfu.ledger_events(led, step=3)
        names = {n for n, _v, _s in check_events(ev)}
        assert {"MFU/achieved", "MFU/roofline_bound", "MFU/step_s",
                "MFU/region.attn", "MFU/region.host"} <= names

    def test_render_flags_truncated_and_bad_reconciliation(self):
        meas = self._measured()
        meas["orphan_s"] = 30e-6
        meas["device_busy_s"] = 90e-6
        led = mfu.ledger(self.ROOFLINE, meas, step_s=100e-6,
                         truncated_trace=True)
        out = mfu.render_ledger(led)
        assert "truncated" in out
        assert "orphaned op time" in out
        assert "do not re-sum" in out


# ===================================================================
# roofline partition (jax side)
# ===================================================================
class TestRoofline:
    def _scoped_jaxpr(self):
        import jax
        import jax.numpy as jnp

        def layer(x, w):
            from deepspeedsyclsupport_tpu.monitor.mfu import region_scope

            with region_scope("attn"):
                y = x @ w
            with region_scope("mlp"):
                y = jnp.tanh(y @ w)
            return y

        def loss(w, x):
            def body(c, _):
                return layer(c, w), None
            out, _ = jax.lax.scan(body, x, None, length=3)
            return out.sum()

        return jax.make_jaxpr(jax.grad(loss))(
            jnp.ones((8, 8), jnp.float32), jnp.ones((4, 8), jnp.float32))

    def test_region_costs_through_grad_and_scan(self):
        from deepspeedsyclsupport_tpu.analysis.roofline import region_costs
        from deepspeedsyclsupport_tpu.profiling.flops_profiler import \
            count_jaxpr_flops

        closed = self._scoped_jaxpr()
        costs = region_costs(closed)
        # fwd + transpose both attribute (scan multiplies by 3)
        assert costs["attn"]["flops"] > 0
        assert costs["mlp"]["flops"] > costs["attn"]["flops"]  # tanh bwd
        assert costs["attn"]["hbm_bytes"] > 0
        # region partition conserves the profiler's total FLOP count
        total = sum(c["flops"] for c in costs.values())
        by_prim = count_jaxpr_flops(closed.jaxpr)
        assert total == pytest.approx(sum(by_prim.values()))

    def test_bound_by_verdicts_follow_spec(self):
        from deepspeedsyclsupport_tpu.analysis.roofline import (DeviceSpec,
                                                                roofline_table)

        costs = {"attn": {"flops": 1e9, "hbm_bytes": 1e6, "comm_bytes": 0.0,
                          "n_eqns": 1}}
        slow_compute = DeviceSpec("a", 1e9, 1e6, 1.0)   # 1s compute, 1ms mem
        slow_memory = DeviceSpec("b", 1e15, 1e-3, 1.0)  # mem dominates
        t1 = roofline_table(costs, slow_compute)
        t2 = roofline_table(costs, slow_memory)
        assert t1["regions"]["attn"]["bound_by"] == "compute"
        assert t2["regions"]["attn"]["bound_by"] == "memory"
        assert t1["total_flops"] == pytest.approx(1e9)

    def test_census_bytes_land_in_collective_region(self):
        from deepspeedsyclsupport_tpu.analysis.roofline import (DeviceSpec,
                                                                roofline_table)

        t = roofline_table({}, DeviceSpec("c", 1e12, 100.0, 10.0),
                           census_bytes=10 * 10**9)
        col = t["regions"]["collective"]
        assert col["comm_bytes"] == pytest.approx(10e9)
        assert col["bound_by"] == "comm"
        assert col["achievable_s"] == pytest.approx(1.0)

    def test_device_spec_registry(self):
        from deepspeedsyclsupport_tpu.analysis import roofline as R

        assert {"tpu-v4", "tpu-v5e", "tpu-v6e", "cpu-sim"} <= set(
            R.DEVICE_SPECS)
        spec = R.device_spec()  # cpu backend under tier-1
        assert spec.name == "cpu-sim"
        # calibrated: replaced the placeholder with measured peaks
        assert spec.peak_flops > 0 and spec.hbm_gbps > 0


# ===================================================================
# dslint undeclared-region rule
# ===================================================================
class TestRegionLint:
    def _lint(self, src, relpath="deepspeedsyclsupport_tpu/x.py"):
        import ast

        from deepspeedsyclsupport_tpu.analysis.codelint import \
            UndeclaredRegionName

        rule = UndeclaredRegionName()
        return list(rule.check(relpath, ast.parse(src), src.splitlines()))

    def test_typoed_region_scope_flagged(self):
        vs = self._lint("from m import region_scope\n"
                        "with region_scope('attnn'):\n    pass\n")
        assert len(vs) == 1 and "attnn" in vs[0].message

    def test_typoed_bare_literal_flagged(self):
        vs = self._lint("LABEL = 'mfu.atn'\n")
        assert len(vs) == 1

    def test_declared_regions_pass(self):
        vs = self._lint("from m import region_scope\n"
                        "with region_scope('attn'):\n    pass\n"
                        "L = 'mfu.optimizer'\n")
        assert vs == []

    def test_filenames_and_tests_excluded(self):
        assert self._lint("p = 'mfu.py'\nq = 'mfu_opmap.json'\n") == []
        assert self._lint("x = 'mfu.bogus'\n", relpath="tests/t.py") == []

    def test_suppression(self):
        vs = self._lint(
            "x = 'mfu.bogus'  # dslint: allow(undeclared-region)\n")
        assert vs == []


class TestMfuConfig:
    def test_knobs_parse_and_validate(self):
        from deepspeedsyclsupport_tpu.runtime.config import TelemetryConfig

        c = TelemetryConfig.from_dict({"enabled": True,
                                       "mfu": {"enabled": True, "step": 5}})
        assert c.mfu_enabled and c.mfu_step == 5
        assert not TelemetryConfig.from_dict({}).mfu_enabled
        with pytest.raises(ValueError, match="mfu.step"):
            TelemetryConfig.from_dict({"mfu": {"step": 0}})


# ===================================================================
# engine e2e: capture + ledger + goodput reconciliation
# ===================================================================
def _mfu_engine(tmp_path, attn_impl="auto", topo=None, seq=256, tb=16,
                micro=2, model_name="tiny"):
    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    if topo is None:
        reset_world_topology()
    cfg = get_config(model_name, max_seq_len=seq, attn_impl=attn_impl)
    model = build_model(cfg)
    config = {"train_batch_size": tb,
              "train_micro_batch_size_per_gpu": micro,
              "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
              "steps_per_print": 10_000,
              "telemetry": {"enabled": True, "output_dir": str(tmp_path),
                            "heartbeat": {"enabled": False},
                            "memory_interval_steps": 0,
                            "mfu": {"enabled": True, "step": 3}}}
    engine, _, _, _ = dstpu.initialize(model=model, config=config,
                                       topology=topo)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (tb, seq)).astype(np.int32)}
    return engine, batch


class TestEngineLedgerE2E:
    def test_ledger_reconciles_and_goodput_accounts(self, tmp_path):
        """The satellite contract: per-region measured times re-sum to the
        measured clean-step time within 5%, the window's step lands in
        goodput's productive bucket, and accounting stays ~100%."""
        engine, batch = _mfu_engine(tmp_path)
        for _ in range(5):
            engine.train_batch(batch)
        assert engine._mfu_window is not None, "no clean-step window"
        led = engine.mfu_ledger()
        try:
            assert not mfu.validate_ledger(led)
            # reconciliation: regions (host included) re-sum to the step
            assert abs(led["reconciliation"]["frac"] - 1.0) <= 0.05, led[
                "reconciliation"]
            # the model phases are all present and measured
            for region in ("attn", "mlp", "optimizer"):
                assert led["regions"][region]["measured_s"] > 0, region
                assert led["regions"][region]["bound_by"] in (
                    "compute", "memory", "comm")
            # the known CPU-sim profile: under the 8-virtual-device data-
            # parallel mesh the grad sync dominates (collective); the
            # transformer body is the alternative on quieter boxes
            assert led["top_sinks"][0] in ("collective", "attn", "mlp",
                                           "other")
            assert led["achieved_mfu"] is not None
            wf = {w["level"]: w["s"] for w in led["waterfall"]}
            assert wf["hardware_peak"] <= wf["roofline_achievable"]
            # goodput: the window step was a normal productive step and
            # the accounter still sums to ~100% by construction
            s = engine.telemetry.goodput.summary()
            assert s["productive"] >= led["step_s"] * 0.9
            known = sum(s[c] for c in ("productive", "checkpoint",
                                       "compile", "offload_stall",
                                       "startup", "other"))
            assert known / s["total"] >= 0.99
            # offline artifacts persisted next to the trace
            tdir = engine._mfu_trace_dir
            for f in ("mfu_opmap.json", "mfu_roofline.json",
                      "mfu_window.json", "mfu_ledger.json"):
                assert os.path.exists(os.path.join(tdir, f)), f
        finally:
            engine.telemetry.close("test")

    def test_capture_skips_compiling_steps(self, tmp_path):
        """Step 3 recompiles (fresh shape): the window must skip it and
        capture a LATER clean step instead of blessing a compile as the
        clean-step sample."""

        try:
            engine, batch = _mfu_engine(tmp_path, seq=64)
            for _ in range(2):
                engine.train_batch(batch)
            smaller = {"input_ids": batch["input_ids"][:, :32]}
            engine.train_batch(smaller)   # step 3: recompile -> rejected
            assert engine._mfu_window is None
            engine.train_batch(smaller)   # step 4: clean -> captured
            assert engine._mfu_window is not None
            assert engine._mfu_window["step"] == 4
        finally:
            engine.telemetry.close("test")


# ===================================================================
# the pass, and the map the engine publishes for the step it compiled
# ===================================================================
@pytest.fixture(scope="module")
def remat_engine():
    """A tiny engine as the benchmark's training cells configure theirs
    (bf16, AdamW, whole-layer remat, a scanned trunk), one step run."""
    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    reset_world_topology()
    cfg = get_config("tiny", max_seq_len=32)
    config = {"train_batch_size": 8, "bf16": {"enabled": True},
              "optimizer": {"type": "adamw",
                            "params": {"lr": 1e-4, "weight_decay": 0.01}},
              "activation_checkpointing": {},
              "steps_per_print": 10_000}
    engine, _, _, _ = dstpu.initialize(model=build_model(cfg), config=config)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    mfu._PUBLISHED.pop("train_batch_fn", None)
    engine.train_batch(batch)
    return engine, batch


class TestPassAndPublish:
    def test_train_batch_alone_publishes_nothing(self, remat_engine):
        # first in the class: nothing has asked for the compiled step yet
        engine, batch = remat_engine
        engine.train_batch(batch)
        assert mfu.published("train_batch_fn") is None

    def test_compiled_train_step_publishes_under_the_program_name(
            self, remat_engine, monkeypatch):
        engine, _ = remat_engine
        compiled = engine.compiled_train_step()
        # handed over as it is: the text is read when somebody asks
        assert mfu._PUBLISHED["train_batch_fn"] is compiled
        om = mfu.published("train_batch_fn")
        assert om == mfu.build_opmap(compiled.as_text())
        assert "jit_train_batch_fn" in compiled.as_text().split("\n")[0]
        # ... once: the second ask parses nothing
        monkeypatch.setattr(mfu, "build_opmap", None)
        assert mfu.published("train_batch_fn") is om
        assert mfu.published("no_such_program") is None

    @pytest.mark.parametrize("region,passes", [
        # the scanned trunk is what jax.checkpoint wraps: all three
        ("attn", {"fwd", "bwd", "recompute"}),
        ("mlp", {"fwd", "bwd", "recompute"}),
        # around it nothing is recomputed
        ("embed", {"fwd", "bwd"}),
        ("head", {"fwd", "bwd"}),
        ("loss", {"fwd", "bwd"}),
        ("optimizer", {None}),
    ])
    def test_every_region_has_its_passes(self, remat_engine, region, passes):
        engine, _ = remat_engine
        engine.compiled_train_step()
        om = mfu.published("train_batch_fn")
        assert {e["pass"] for e in om.values()
                if e["region"] == region} == passes

    def test_unscoped_instructions_and_the_text_without_percent(
            self, remat_engine):
        engine, _ = remat_engine
        text = engine.compiled_train_step().as_text()
        om = mfu.build_opmap(text)
        assert mfu.build_opmap(text.replace("%", "")) == om
        # norm chains of a rematted layer: no region, but a pass
        other = {e["pass"] for e in om.values() if e["region"] == "other"}
        assert {"bwd", "recompute", None} <= other
        assert "fwd" not in other
        assert all(e["pass"] is None or e["pass"] in mfu.PASSES
                   for e in om.values())

    def test_the_ledger_reads_the_published_map(self, remat_engine,
                                                monkeypatch):
        """``mfu_ledger()`` asks ``published`` and builds no second map."""
        engine, _ = remat_engine
        seen = []
        monkeypatch.setattr(mfu, "measure_regions", lambda ev, om, steps=1:
                            seen.append(om) or {"regions": {}})
        monkeypatch.setattr(mfu, "find_trace", lambda root: "t.json")
        monkeypatch.setattr(mfu, "parse_trace", lambda path: (
            [], {"truncated": False}))
        engine._mfu_window = {"step": 1, "step_s": 1.0, "steps": 1,
                              "trace_dir": "unused"}
        try:
            engine.mfu_ledger(persist=False)
        finally:
            engine._mfu_window = None
        assert len(seen) == 1
        assert seen[0] is mfu.published("train_batch_fn")


# ===================================================================
# ring attn_impl wiring + the two-arm A/B under the ledger
# ===================================================================
class TestRingInner:
    def _qkv(self, s=32, h=2, kvh=1, d=8, b=4):
        # small on purpose: the flash inner runs in INTERPRET mode off-TPU,
        # whose cost scales with pallas grid cells (b x h x blocks)
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        return (jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.float32),
                jnp.asarray(rng.normal(0, 1, (b, s, kvh, d)), jnp.float32),
                jnp.asarray(rng.normal(0, 1, (b, s, kvh, d)), jnp.float32))

    def test_flash_inner_matches_inline_and_reference(self):
        import jax
        import jax.numpy as jnp

        from deepspeedsyclsupport_tpu.comm.topology import (
            build_topology, reset_world_topology)
        from deepspeedsyclsupport_tpu.models.layers import \
            reference_attention
        from deepspeedsyclsupport_tpu.parallel.ring_attention import \
            ring_attention

        try:
            reset_world_topology()
            build_topology(dp=4, sp=2)
            q, k, v = self._qkv()
            # (under jit, as the engine runs it: eagerly the shard_map walks
            # the interpreted kernel op by op, 31 s for the same numbers)
            for causal in (True, False):
                ref = reference_attention(q, k, v, causal=causal)
                got = jax.jit(lambda a, b, c: ring_attention(
                    a, b, c, causal=causal, inner="flash"))(q, k, v)
                np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                           atol=2e-5)
            # gradients flow through the lse combine exactly
            def loss(fn):
                return lambda a, b, c: (fn(a, b, c) *
                                        jnp.arange(8)).sum()
            g_fl = jax.jit(jax.grad(loss(lambda a, b, c: ring_attention(
                a, b, c, causal=True, inner="flash")), (0, 1, 2)))(q, k, v)
            g_ref = jax.grad(loss(lambda a, b, c: reference_attention(
                a, b, c, causal=True)), (0, 1, 2))(q, k, v)
            for a, b in zip(g_fl, g_ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=5e-4)
        finally:
            from deepspeedsyclsupport_tpu.comm.topology import \
                reset_world_topology as rwt

            rwt()

    def test_attention_dispatch_colon_syntax(self):
        import jax

        from deepspeedsyclsupport_tpu.comm.topology import (
            build_topology, reset_world_topology)
        from deepspeedsyclsupport_tpu.models.layers import (
            attention, reference_attention)

        try:
            reset_world_topology()
            build_topology(dp=4, sp=2)
            q, k, v = self._qkv()
            ref = reference_attention(q, k, v, causal=True)
            # the flash-inner arm is priced by the A/B e2e below (interpret
            # mode is expensive); the dispatch seam itself is impl-agnostic
            for impl in ("ring:xla",):
                got = jax.jit(lambda a, b, c: attention(
                    a, b, c, impl=impl, causal=True))(q, k, v)
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(ref), atol=2e-5)
        finally:
            from deepspeedsyclsupport_tpu.comm.topology import \
                reset_world_topology as rwt

            rwt()

    @pytest.mark.slow  # two full engine compiles with interpret-mode
    def test_ring_ab_under_the_ledger(self, tmp_path):  # pallas (~40s)
        """The acceptance A/B: two arms (inline vs Pallas-flash inner) run
        end-to-end through the engine with the ledger on — per-region
        attention time reported for BOTH arms."""
        from deepspeedsyclsupport_tpu.comm.topology import build_topology

        engines = []
        try:
            attn_s = {}
            for arm, impl in (("xla", "ring:xla"), ("flash", "ring:flash")):
                engine, batch = _mfu_engine(
                    tmp_path / arm, attn_impl=impl,
                    topo=build_topology(dp=4, sp=2), seq=32, tb=8,
                    micro=2)
                engines.append(engine)
                for _ in range(3):
                    engine.train_batch(batch)
                led = engine.mfu_ledger()
                attn_s[arm] = led["regions"]["attn"]["measured_s"]
            assert attn_s["xla"] > 0 and attn_s["flash"] > 0
        finally:
            for e in engines:
                e.telemetry.close("test")


# ===================================================================
# offline tools
# ===================================================================
def _jax_blocked_env(tmp_path):
    blocker = tmp_path / "nojax"
    blocker.mkdir(exist_ok=True)
    (blocker / "jax.py").write_text(
        "raise ImportError('jax blocked: mfu_report must be stdlib-only')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(blocker)
    return env


class TestMfuReportCLI:
    def test_fixture_renders_with_jax_import_blocked(self, tmp_path):
        """The login-node contract on the checked-in miniature fixture."""
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "mfu_report.py"),
             FIXTURE], env=_jax_blocked_env(tmp_path),
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "MFU ledger" in out.stdout
        assert "gap waterfall" in out.stdout
        assert "top sinks: optimizer" in out.stdout
        assert "97.1% accounted" in out.stdout

    def test_truncated_trace_flagged_not_fatal(self, tmp_path):
        """Same contract as pod.py: a torn trace.json.gz (killed
        mid-write) salvages and flags instead of crashing."""
        work = tmp_path / "torn"
        shutil.copytree(FIXTURE, work)
        gz = work / "mini.trace.json.gz"
        raw = gz.read_bytes()
        gz.write_bytes(raw[:int(len(raw) * 0.7)])
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "mfu_report.py"),
             str(work)], env=_jax_blocked_env(tmp_path),
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "truncated" in (out.stdout + out.stderr)
        assert "MFU ledger" in out.stdout

    def test_empty_dir_exits_2(self, tmp_path):
        empty = tmp_path / "empty"
        empty.mkdir()
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "mfu_report.py"),
             str(empty)], env=_jax_blocked_env(tmp_path),
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 2

    def test_json_output_schema(self, tmp_path):
        dst = tmp_path / "led.json"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "mfu_report.py"),
             FIXTURE, "--json", str(dst)], env=_jax_blocked_env(tmp_path),
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0
        led = json.loads(dst.read_text())
        assert not mfu.validate_ledger(led)
        assert led["regions"]["attn"]["measured_s"] == pytest.approx(30e-6)

