"""HLO-level comms accounting (VERDICT r3 #6): the XLA-partitioner-inserted
collectives of a sharded train step, parsed from the compiled program and
merged into comms_logger.log_summary() (reference ``comm/comm.py:422``,
``utils/comms_logging.py:108`` show_straggler)."""
import numpy as np

import deepspeedsyclsupport_tpu as dstpu
from deepspeedsyclsupport_tpu.comm.comms_logging import comms_logger
from deepspeedsyclsupport_tpu.comm.hlo_comms import (parse_collectives,
                                                     summarize_collectives)

from .simple_model import SimpleModel, random_dataset, simple_config


class TestHloParser:
    HLO = """
  %ag.1 = f32[8,128]{1,0} all-gather(f32[2,128]{1,0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %ar = bf16[1024]{0} all-reduce(bf16[1024]{0} %x), replica_groups=[2,4]<=[8], to_apply=%add
  %rs = f32[2,64]{1,0} reduce-scatter(f32[8,64]{1,0} %y), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %ags = (f32[512]{0}, f32[2048]{0}) all-gather-start(f32[512]{0} %z), replica_groups={{0,1,2,3}}
  %agd = f32[2048]{0} all-gather-done((f32[512]{0}, f32[2048]{0}) %ags)
  %cp = u32[16]{0} collective-permute(u32[16]{0} %w), source_target_pairs={{0,1},{1,0}}
  %notacoll = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""

    def test_parse_finds_all_and_only_collectives(self):
        recs = parse_collectives(self.HLO)
        ops = [r["op"] for r in recs]
        assert ops == ["all-gather", "all-reduce", "reduce-scatter",
                       "all-gather", "collective-permute"]

    def test_bytes_and_groups(self):
        recs = parse_collectives(self.HLO)
        ag = recs[0]
        assert ag["bytes"] == 8 * 128 * 4
        assert ag["group_size"] == 4
        ar = recs[1]
        assert ar["bytes"] == 1024 * 2 and ar["group_size"] == 4
        # start/done pair counted once; tuple result counts only the OUTPUT
        # element (the first is the aliased input, not wire traffic)
        ags = recs[3]
        assert ags["bytes"] == 2048 * 4
        cp = recs[4]
        assert cp["bytes"] == 16 * 4

    def test_summarize(self):
        s = summarize_collectives(self.HLO)
        assert s["all-gather"]["count"] == 2
        assert s["all-gather"]["total_bytes"] == 8 * 128 * 4 + 2048 * 4
        assert s["reduce-scatter"]["count"] == 1


class TestTpuTextAndLoops:
    """What the chip's compiler writes and the CPU's does not: layouts with
    parentheses of their own inside tuples, combined (tuple-result) ops, and
    a layer scan whose per-layer collectives sit once in the loop body."""
    HLO = """
%cond.1 (p: (s32[], bf16[6,1024,4096])) -> pred[] {
  %c = s32[]{:T(128)} constant(6)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i, %c), direction=LT
}

%body.1 (p.1: (s32[], bf16[6,1024,4096])) -> (s32[], bf16[6,1024,4096]) {
  %ag = bf16[4096,4096]{1,0:T(8,128)(2,1)} all-gather(%slice), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}
  %cps = (bf16[96,1024]{1,0:T(8,128)(2,1)}, bf16[96,1024]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%s2), source_target_pairs={{0,1}}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = (s32[]{:T(128)}, bf16[6,1024,4096]{2,1,0:T(8,128)(2,1)}) while(%t), condition=%cond.1, body=%body.1
  %ar = (f32[2048]{0:T(1024)}, f32[2048,256]{1,0:T(8,128)}) all-reduce(%g0, %g1), channel_id=2, replica_groups=[1,4]<=[4], to_apply=%add
}
"""

    def test_loop_body_ops_run_once_per_trip(self):
        recs = {r["op"]: r for r in parse_collectives(self.HLO)}
        assert recs["all-gather"]["executions"] == 6
        assert recs["collective-permute"]["executions"] == 6
        assert recs["all-reduce"]["executions"] == 1
        s = summarize_collectives(self.HLO)
        assert s["all-gather"]["total_bytes"] == 6 * 4096 * 4096 * 2

    def test_tuple_results_with_tpu_layouts(self):
        recs = {r["op"]: r for r in parse_collectives(self.HLO)}
        # async start: (operand, output, context scalars) -> the output
        assert recs["collective-permute"]["bytes"] == 96 * 1024 * 2
        # combined all-reduce: every element of the tuple is payload
        ar = recs["all-reduce"]
        assert [p["elems"] for p in ar["parts"]] == [2048, 2048 * 256]
        assert ar["bytes"] == (2048 + 2048 * 256) * 4
        assert ar["shape"] == "(f32[2048], f32[2048,256])"


class TestEngineSummary:
    def _engine(self, stage, model=None):
        model = model or SimpleModel(hidden_dim=64)
        cfg = simple_config(train_batch_size=8,
                            train_micro_batch_size_per_gpu=1,
                            zero_optimization={"stage": stage},
                            comms_logger={"enabled": True})
        engine, _, _, _ = dstpu.initialize(model=model, config=cfg)
        return engine

    def test_stage3_shows_partitioner_traffic(self):
        """The stage-3 step on the flagship model must surface all-gather
        (param gathers) and reduce-scatter/all-reduce (grad partitioning)
        traffic that never touches the comm façade. (A tiny MLP is NOT used
        here: XLA may legally replicate it wholesale and emit no
        collectives at all.)"""
        import jax

        from deepspeedsyclsupport_tpu.models import build_model

        comms_logger.reset()
        engine = self._engine(stage=3, model=build_model("tiny"))
        ids = jax.random.randint(jax.random.PRNGKey(0), (8, 32), 0, 512)
        batch = {"input_ids": ids}
        engine.train_batch(batch)
        summary = engine.xla_comms_summary(log=False)
        assert "all-gather" in summary, summary
        assert summary["all-gather"]["total_bytes"] > 0
        reduced = {k: v for k, v in summary.items()
                   if k in ("reduce-scatter", "all-reduce")}
        assert reduced and sum(v["total_bytes"]
                               for v in reduced.values()) > 0
        # merged into the shared logger under xla:: keys
        snap = comms_logger.snapshot()
        assert any(k.startswith("xla::all-gather") for k in snap)
        # idempotent: second summary does not double-count
        engine.xla_comms_summary(log=False)
        snap2 = comms_logger.snapshot()
        assert snap == snap2

    def test_summary_table_and_straggler_column(self):
        import jax

        from deepspeedsyclsupport_tpu.models import build_model

        comms_logger.reset()
        engine = self._engine(stage=2, model=build_model("tiny"))
        batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(1),
                                                 (8, 32), 0, 512)}
        engine.train_batch(batch)
        engine.train_batch(batch)
        table = comms_logger.log_summary(show_straggler=True)
        assert "wall-clock (per host)" in table
        assert "train_batch" in table
        engine.xla_comms_summary(log=False)
        table = comms_logger.log_summary()
        assert "xla::" in table

    def test_requires_enabled_logger(self):
        import pytest

        model = SimpleModel(hidden_dim=16)
        engine, _, _, _ = dstpu.initialize(
            model=model, config=simple_config(train_batch_size=8,
                                              train_micro_batch_size_per_gpu=1))
        batch = random_dataset(8, hidden_dim=16, n_batches=1, seed=2)[0]
        engine.train_batch(batch)
        with pytest.raises(RuntimeError, match="comms_logger"):
            engine.xla_comms_summary()
