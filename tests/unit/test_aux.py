"""Aux-ring tests: flops profiler, elasticity, compression, autotuner
(reference analogs: ``tests/unit/{profiling,elasticity,compression,autotuning}``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeedsyclsupport_tpu as dstpu
from deepspeedsyclsupport_tpu.autotuning import Autotuner
from deepspeedsyclsupport_tpu.compression import (compress, dequantize_int8,
                                                  fake_quant, quantize_int8)
from deepspeedsyclsupport_tpu.elasticity import (ElasticityConfigError,
                                                 ElasticityError,
                                                 compute_elastic_config,
                                                 get_compatible_gpus)
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.profiling import get_model_profile, profile_fn
from tests.unit.simple_model import SimpleModel, simple_config


# ------------------------------------------------------------------- profiler
class TestFlopsProfiler:
    def test_matmul_exact(self):
        a = jnp.zeros((8, 32))
        b = jnp.zeros((32, 16))
        p = profile_fn(lambda x, y: x @ y, a, b)
        assert p.total_flops == 2 * 8 * 32 * 16
        assert "dot_general" in p.by_primitive

    def test_scan_multiplies(self):
        w = jnp.zeros((4, 16, 16))  # 4 layers

        def fn(w, x):
            return jax.lax.scan(lambda h, wl: (h @ wl, None), x, w)[0]

        p = profile_fn(fn, w, jnp.zeros((2, 16)))
        assert p.by_primitive["dot_general"] == 4 * 2 * 2 * 16 * 16

    def test_model_profile_scales_with_seq(self):
        model = build_model("tiny")
        p1 = get_model_profile(model, batch_size=1, seq_len=32)
        p2 = get_model_profile(model, batch_size=1, seq_len=64)
        assert p2.total_flops > 1.9 * p1.total_flops
        assert p1.total_params == sum(
            int(np.prod(np.shape(l)))
            for l in jax.tree_util.tree_leaves(model.init_params()))

    def test_reduction_costed_by_input(self):
        p = profile_fn(lambda x: jnp.sum(x), jnp.zeros((64, 64)))
        assert p.by_primitive["reduce_sum"] == 64 * 64

    def test_engine_hook_writes_profile(self, tmp_path):
        out = tmp_path / "flops.txt"
        engine, *_ = dstpu.initialize(
            model=SimpleModel(),
            config=simple_config(flops_profiler={
                "enabled": True, "profile_step": 1,
                "output_file": str(out)}))
        batch = {"x": np.zeros((2, 32), np.float32),
                 "y": np.zeros((2, 32), np.float32)}
        engine.train_batch(batch)
        assert out.exists() and "flops" in out.read_text()
        assert engine.flops_profiler.profile.total_flops > 0


# ------------------------------------------------------------------ elasticity
class TestElasticity:
    def test_compatible_gpus(self):
        batch, gpus = get_compatible_gpus(
            max_acceptable_batch_size=10000,
            micro_batches=[8, 12, 16, 17], min_gpus=32, max_gpus=1500)
        # every valid gpu count must evenly produce the batch from some micro
        for g in gpus:
            assert any(batch % (mb * g) == 0 for mb in [8, 12, 16, 17])
        assert batch <= 10000 and gpus

    def test_full_config_resolution(self):
        cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 2048,
                              "micro_batch_sizes": [2, 4, 8],
                              "min_gpus": 1, "max_gpus": 512}}
        r = compute_elastic_config(cfg, target_deployment_size=64)
        assert r.final_batch_size % (r.micro_batch_per_gpu * 64) == 0
        assert r.final_batch_size == (r.micro_batch_per_gpu *
                                      r.gradient_accumulation_steps * 64)

    def test_disabled_raises(self):
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config({"elasticity": {"enabled": False}})

    def test_mp_indivisible_deployment_raises(self):
        cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                              "micro_batch_sizes": [2],
                              "model_parallel_size": 2}}
        with pytest.raises(ElasticityError):
            compute_elastic_config(cfg, target_deployment_size=65)

    def test_incompatible_deployment_raises(self):
        cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 8,
                              "micro_batch_sizes": [4], "max_gpus": 2}}
        with pytest.raises(ElasticityError):
            compute_elastic_config(cfg, target_deployment_size=3)


# ----------------------------------------------------------------- compression
class TestQuantization:
    def test_int8_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
        q, s = quantize_int8(x)
        y = dequantize_int8(q, s)
        assert q.dtype == jnp.int8
        assert float(jnp.abs(x - y).max()) <= float(s) * 0.5 + 1e-6

    def test_blockwise_tighter_than_per_tensor(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 256)) * \
            jnp.linspace(0.01, 10.0, 4)[:, None]  # wildly varying rows
        qt, st = quantize_int8(x)
        qb, sb = quantize_int8(x, group_size=64)
        err_t = float(jnp.abs(x - dequantize_int8(qt, st)).mean())
        err_b = float(jnp.abs(x - dequantize_int8(qb, sb, group_size=64)).mean())
        assert err_b < err_t

    def test_fake_quant_ste_gradient(self):
        x = jnp.linspace(-1, 1, 32)
        g = jax.grad(lambda v: jnp.sum(fake_quant(v) * 2.0))(x)
        np.testing.assert_allclose(np.asarray(g), 2.0)  # straight-through

    def test_compress_config_driven(self):
        params = {"attn": {"wq": jax.random.normal(jax.random.PRNGKey(2),
                                                   (32, 32))},
                  "norm": {"scale": jnp.ones((32,))}}
        cfg = {"compression_training": {"sparse_pruning": {
            "shared_parameters": {"enabled": True},
            "different_groups": {"sp1": {"params": {"dense_ratio": 0.25},
                                         "modules": ["attn"]}}}}}
        out = compress(params, cfg)
        w = np.asarray(out["attn"]["wq"])
        density = (w != 0).mean()
        assert 0.2 <= density <= 0.3
        np.testing.assert_array_equal(np.asarray(out["norm"]["scale"]),
                                      np.ones((32,)))  # 1-D untouched

    def test_per_group_settings_respected(self):
        """Different groups keep their own settings (regression: first group's
        params were once applied to every matched module)."""
        rng = jax.random.PRNGKey(3)
        params = {"attn": {"w": jax.random.normal(rng, (64, 64))},
                  "mlp": {"w": jax.random.normal(rng, (64, 64))}}
        cfg = {"compression_training": {"sparse_pruning": {
            "shared_parameters": {"enabled": True},
            "different_groups": {
                "sp1": {"params": {"dense_ratio": 0.75}, "modules": ["attn*"]},
                "sp2": {"params": {"dense_ratio": 0.25}, "modules": ["mlp*"]},
            }}}}
        out = compress(params, cfg)
        d_attn = (np.asarray(out["attn"]["w"]) != 0).mean()
        d_mlp = (np.asarray(out["mlp"]["w"]) != 0).mean()
        assert 0.7 <= d_attn <= 0.8
        assert 0.2 <= d_mlp <= 0.3


class TestStructuredCompression:
    """Head/row/channel pruning + layer reduction (VERDICT r2 #10;
    reference ``compression/compress.py`` + ``basic_layer`` masks)."""

    def test_row_pruning_masks_output_columns(self):
        from deepspeedsyclsupport_tpu.compression import compress

        w = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        cfg = {"compression_training": {"row_pruning": {
            "shared_parameters": {"enabled": True},
            "different_groups": {"rp1": {"params": {"dense_ratio": 0.25},
                                         "modules": ["mlp*"]}}}}}
        out = np.asarray(compress({"mlp": {"fc1": w}}, cfg)["mlp"]["fc1"])
        col_alive = (np.abs(out).sum(axis=0) > 0)
        assert col_alive.sum() == 8                 # 25% of 32 output cols
        # kept columns are the highest-importance ones, untouched
        imp = np.abs(np.asarray(w)).sum(axis=0)
        assert set(np.where(col_alive)[0]) == set(np.argsort(imp)[-8:])
        np.testing.assert_array_equal(out[:, col_alive],
                                      np.asarray(w)[:, col_alive])

    def test_channel_pruning_masks_input_rows(self):
        from deepspeedsyclsupport_tpu.compression import compress

        w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
        cfg = {"compression_training": {"channel_pruning": {
            "shared_parameters": {"enabled": True},
            "different_groups": {"cp1": {"params": {"dense_ratio": 0.5},
                                         "modules": ["*"]}}}}}
        out = np.asarray(compress({"w": w}, cfg)["w"])
        assert (np.abs(out).sum(axis=1) > 0).sum() == 16

    def test_head_pruning_one_mask_from_wo(self):
        """All attention matrices of a module share ONE head mask derived
        from the output projection (disjoint per-matrix masks would zero
        the whole attention output), per layer on stacked leaves."""
        from deepspeedsyclsupport_tpu.compression import compress

        h, hd, d, L = 8, 4, 32, 2
        rng = jax.random.PRNGKey(2)
        wo = jax.random.normal(rng, (L, h * hd, d))
        wq = jax.random.normal(jax.random.fold_in(rng, 1), (L, d, h * hd))
        cfg = {"compression_training": {"head_pruning": {
            "shared_parameters": {"enabled": True, "num_heads": h},
            "different_groups": {"hp1": {"params": {"dense_ratio": 0.5},
                                         "modules": ["*attn*"]}}}}}
        out = compress({"layers": {"attn": {"wo": wo, "wq": wq}}},
                       cfg)["layers"]["attn"]
        for layer in range(L):
            wo_heads = np.asarray(out["wo"][layer]).reshape(h, hd, d)
            wq_heads = np.asarray(out["wq"][layer]).reshape(d, h, hd)
            dead_o = {i for i in range(h) if not np.abs(wo_heads[i]).sum()}
            dead_q = {i for i in range(h)
                      if not np.abs(wq_heads[:, i]).sum()}
            assert len(dead_o) == 4
            assert dead_o == dead_q  # one mask, not per-matrix masks
            # the mask follows wo's importance in THIS layer
            imp = np.abs(np.asarray(wo[layer])).reshape(h, -1).sum(axis=1)
            assert dead_o == set(np.argsort(imp)[:4])

    def test_head_pruning_requires_num_heads(self):
        from deepspeedsyclsupport_tpu.compression import (
            get_compression_config)

        with pytest.raises(ValueError):
            get_compression_config({"compression_training": {
                "head_pruning": {"shared_parameters": {"enabled": True}}}})

    def test_layer_reduction_student(self):
        """Student keeps the chosen teacher layers and still runs."""
        from deepspeedsyclsupport_tpu.compression import (
            apply_layer_reduction)
        from deepspeedsyclsupport_tpu.models import CausalLM

        model = build_model("tiny", num_layers=4)
        params = model.init_params(jax.random.PRNGKey(3))
        cfg = {"compression_training": {"layer_reduction": {
            "enabled": True, "keep_number_layer": 2,
            "teacher_layer": [0, 3]}}}
        new_cfg, new_params = apply_layer_reduction(model.config, params,
                                                    cfg)
        assert new_cfg.num_layers == 2
        lw = jax.tree_util.tree_leaves(new_params["layers"])[0]
        assert lw.shape[0] == 2
        old = jax.tree_util.tree_leaves(params["layers"])[0]
        np.testing.assert_array_equal(np.asarray(lw[1]), np.asarray(old[3]))
        student = CausalLM(new_cfg)
        ids = jnp.asarray(np.ones((2, 8), np.int32))
        logits = student.apply(new_params, ids)
        assert logits.shape == (2, 8, new_cfg.vocab_size)

    def test_layer_reduction_validates_indices(self):
        from deepspeedsyclsupport_tpu.compression import (
            apply_layer_reduction)

        model = build_model("tiny")
        params = model.init_params(jax.random.PRNGKey(4))
        with pytest.raises(ValueError):
            apply_layer_reduction(model.config, params, {
                "compression_training": {"layer_reduction": {
                    "enabled": True, "teacher_layer": [0, 99]}}})


# ------------------------------------------------------------------ autotuner
class TestAutotuner:
    def test_picks_best_and_survives_failures(self):
        model = SimpleModel()

        def make_batch(bs):
            return {"x": np.zeros((bs, 32), np.float32),
                    "y": np.zeros((bs, 32), np.float32)}

        tuner = Autotuner(
            model, simple_config(),
            make_batch,
            space={"train_micro_batch_size_per_gpu": [2, -1]},  # -1 → invalid
            steps=2, warmup=1)
        res = tuner.tune()
        assert res.best_throughput > 0
        assert res.best_config["train_micro_batch_size_per_gpu"] == 2
        bad = [t for t in res.trials
               if t["train_micro_batch_size_per_gpu"] == -1]
        assert bad and bad[0]["throughput"] == float("-inf")

    def test_multi_dim_space_with_memory_pruning(self):
        """VERDICT r2 #8: zero × remat × offload × mbs dims, with
        memory-model pruning keeping over-budget candidates from ever
        compiling, and the tuner still finding the known-best config."""
        from deepspeedsyclsupport_tpu.models import build_model

        model = build_model("tiny", max_seq_len=64)

        def make_batch(bs):
            return {"input_ids": np.ones((bs, 32), np.int32)}

        space = {
            "train_micro_batch_size_per_gpu": [1, 1024],  # 1024: over budget
            "zero_optimization.stage": [0, 2],
            "activation_checkpointing.enabled": [False, True],
            "zero_optimization.offload_optimizer.device": ["none", "cpu"],
        }
        # budget sized so mbs=1024 candidates prune out (tiny model:
        # ~0.14M params; activations at mbs=1024 predict ~270 MB)
        tuner = Autotuner(model, {"train_batch_size": 8,
                                  "optimizer": {"type": "adam",
                                                "params": {"lr": 1e-3}}},
                          make_batch, space=space, steps=1, warmup=1,
                          hbm_bytes=2e8, seq_len=32)
        res = tuner.tune()
        assert res.best_throughput > 0
        assert res.best_config["train_micro_batch_size_per_gpu"] == 1
        # every mbs=1024 candidate was pruned by the model, never measured
        big = [t for t in res.trials
               if t["train_micro_batch_size_per_gpu"] == 1024]
        assert big and all(t.get("pruned") for t in big)
        # at least one offload trial and one remat trial actually measured
        measured = [t for t in res.trials if not t.get("pruned")]
        assert any(t["zero_optimization.offload_optimizer.device"] == "cpu"
                   for t in measured)
        assert any(t["activation_checkpointing.enabled"]
                   for t in measured)


class TestNuma:
    """NUMA binding (reference ``deepspeed/utils/numa.py`` +
    ``--bind_cores_to_rank``)."""

    def test_parse_and_compact_roundtrip(self):
        from deepspeedsyclsupport_tpu.utils.numa import (_compact,
                                                         parse_range_list)

        assert parse_range_list("0-3,8,10-11") == [0, 1, 2, 3, 8, 10, 11]
        assert _compact([0, 1, 2, 3, 8, 10, 11]) == "0-3,8,10-11"
        with pytest.raises(ValueError):
            parse_range_list("5-2")

    def test_numactl_cmd_slices_cores(self):
        from deepspeedsyclsupport_tpu.utils.numa import get_numactl_cmd

        nodes = [[0, 1, 2, 3], [4, 5, 6, 7]]  # two numa nodes
        cmd0, cores0 = get_numactl_cmd(None, 2, 0, numa_nodes=nodes)
        cmd1, cores1 = get_numactl_cmd(None, 2, 1, numa_nodes=nodes)
        assert cores0 == [0, 1, 2, 3] and cores1 == [4, 5, 6, 7]
        assert cmd0 == ["numactl", "-C", "0-3", "-m", "0"]
        assert cmd1 == ["numactl", "-C", "4-7", "-m", "1"]
        # explicit core list, uneven split: last rank takes the remainder
        cmd, cores = get_numactl_cmd("0-4", 2, 1, numa_nodes=nodes)
        assert cores == [2, 3, 4]

    def test_launcher_binds_cores(self, tmp_path):
        from deepspeedsyclsupport_tpu.launcher.runner import (_command,
                                                              build_world)

        class A:
            hostfile = None
            num_nodes = 1
            num_procs = 2
            include = exclude = None
            master_addr = None
            master_port = 29500
            module = False
            user_script = "train.py"
            user_args = []
            bind_cores_to_rank = True
            bind_core_list = "0-7"
            dry_run = True  # skip the numactl-binary presence gate

        world = build_world(A)
        assert [e["LOCAL_RANK"] for e in world] == ["0", "1"]
        c0 = _command(A, world[0])
        c1 = _command(A, world[1])
        assert c0[:3] == ["numactl", "-C", "0-3"]
        assert c1[:3] == ["numactl", "-C", "4-7"]
        assert c0[-1] == "train.py"
        # remote host without an explicit core list must be rejected — the
        # launcher cannot read a remote machine's NUMA topology
        env = dict(world[0])
        env["host"] = "worker-1"
        A.bind_core_list = None
        with pytest.raises(ValueError):
            _command(A, env)
        A.bind_core_list = "0-7"
        rc = _command(A, env)
        assert rc[0] == "ssh" and "numactl -C 0-3" in rc[-1]
        assert "-m" not in rc[-1].split("train.py")[0].split("numactl")[1]

    def test_numa_cores_fallback(self, tmp_path):
        from deepspeedsyclsupport_tpu.utils.numa import get_numa_cores

        # nonexistent sysfs dir → single synthetic node with all cpus
        nodes = get_numa_cores(str(tmp_path / "nope"))
        assert len(nodes) == 1 and len(nodes[0]) >= 1


class TestTuneChainTimer:
    """tools/tpu_tune.py carries its own chain timer (the block-size sweeps'
    clock) and imports nothing from outside the package and jax."""

    @pytest.fixture
    def tune(self, monkeypatch):
        import importlib.util
        import os
        import sys

        monkeypatch.setattr(sys, "path", list(sys.path))  # the tool prepends
        path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                            "tpu_tune.py")
        spec = importlib.util.spec_from_file_location("tpu_tune", path)
        tune = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tune)
        assert "bench" not in sys.modules
        return tune

    # one iteration has no chain to subtract the floor from: the verdict
    # must say so and not report a difference of two samples
    @pytest.mark.parametrize("iters,verdicts", [
        (4, ("chained", "dispatch_bound")), (1, ("dispatch_bound",))])
    def test_chain_timer_times_a_matmul_chain(self, tune, iters, verdicts):
        b = jnp.eye(16, dtype=jnp.float32)
        dt, how = tune._bench_chain(lambda x, b: x @ b, jnp.ones((16, 16)),
                                    (b,), iters)
        assert dt > 0 and how in verdicts

    def test_the_dsa_sweep_runs_every_step_and_holds_the_twins(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py dsa`` at a tiny cell, the kernels interpreted and
        the profiler's reading stubbed (what the chip gives): every step of
        the one-token rows' route and of the atom's tile is built and run,
        a keys-a-step candidate is in force while ITS step is traced and
        not after, and ``--parity`` reads no difference from the twins."""
        import functools
        import json

        monkeypatch.setattr(tune, "DSA_CELL", dict(
            seqs=4, table=1024, heads=2, dim=8, topk=64, atom=8))
        monkeypatch.setattr(tune, "DSA_CONTEXTS", (512, 1024))
        load, asked = tune._load_op, []

        def interpreted(root, op, name):
            mod = load(root, op, name)
            scores = mod.index_scores_pallas

            def listening(q, *a, **kw):
                asked.append(mod.score_keys(q.shape[1], 2, 8, 2, 1024))
                return scores(q, *a, **kw, interpret=True)
            mod.index_scores_pallas = listening
            mod.select_topk_pallas = functools.partial(
                mod.select_topk_pallas, interpret=True)
            return mod

        def reading(steps, args, **_kw):
            for step in steps.values():
                jax.block_until_ready(step(*args))
            return {name: {"kernel": 1.0, "xla": 0.25} for name in steps}

        monkeypatch.setattr(tune, "_load_op", interpreted)
        monkeypatch.setattr(tune, "_traced_kernels", reading)
        tune.dsa(["--keys", "128", "256", "--parity"])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        parity = {r["what"]: r for r in out if r["section"] == "dsa_parity"}
        assert parity["rows_scores"]["worst"] < 1e-6
        assert all(parity[w]["mask_differs"] == 0
                   and parity[w]["positions_differ"] == 0
                   for w in ("rows_select", "rows_select_ties"))
        assert parity["rows_select"]["kept"] == [64, 0, 48, 64]
        rows, atom = [r for r in out if r["section"] == "dsa"]
        assert not rows["failed"] and not atom["failed"]
        assert set(rows["us_a_call"]) == {
            "rows_scores_128", "rows_scores_256", "rows_scores_tree",
            "rows_select_tree", "rows_positions_tree",
            "rows_xla_scores_and_top_k"}
        assert set(atom["us_a_call"]) == {"atom_scores_tree",
                                          "atom_select_tree"}
        assert rows["us_a_call"]["rows_select_tree"] == {"512": 1000.0,
                                                         "1024": 1000.0}
        assert rows["xla_us_a_call"]["rows_positions_tree"]["512"] == 250.0
        # parity's call, the two candidates, the rule's own, the atom's
        assert asked == [1024, 128, 256, 1024, 1024]


    def test_the_conv_sweep_runs_every_form_and_holds_the_kernel(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py conv`` at a tiny cell, the kernel interpreted and
        the profiler's reading stubbed: the XLA form, the rule's own kernel
        and every (slots, channels) candidate are built and run, each
        program starts its sum from a number of its own (two that read the
        same would share one executable), and ``--parity`` reads no
        difference in the results or the tails."""
        import functools
        import json

        from deepspeedsyclsupport_tpu.ops import ssm

        monkeypatch.setattr(tune, "CONV_CELLS", {"tiny": dict(
            layers=2, slots=21, rows=12, channels=256, bias=True)})
        monkeypatch.setitem(ssm.CONV_STEPS, "pallas", functools.partial(
            ssm._conv_step_pallas, interpret=True))
        firsts = {}

        def reading(steps, args, carry=None, **_kw):
            for name, step in steps.items():
                carry, out = step(carry, *args)
                firsts[name] = float(out[10, -1])   # a row on the sink
            return {name: {"kernel": 0.5, "xla": 0.25,
                           "calls": {"kernel": 6}}
                    if name.startswith("kernel") else {"xla": 2.0}
                    for name in steps}

        monkeypatch.setattr(tune, "_traced_kernels", reading)
        tune.conv(["--cell", "tiny", "--slots", "16", "32", "--lanes",
                   "128", "--parity"])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        parity, = [r for r in out if r["section"] == "conv_parity"]
        assert parity["out_err"] < 1e-5 and parity["tails_differ"] == 0
        assert parity["out_max"] > 1
        table, = [r for r in out if r["section"] == "conv"]
        assert not table["failed"] and table["rule"] == {"slots": 16,
                                                         "lanes": 256}
        assert set(table["rows"]) == {"xla", "kernel_tree", "kernel_16x128",
                                      "kernel_32x128"}
        # the kernel's reading is a layer's, XLA's a whole program's
        assert table["rows"]["kernel_tree"]["ms_a_layer"] == 0.625
        assert table["rows"]["xla"]["ms_a_layer"] == 1.0
        moved = 2 * 12 * 3 * 256 * 2
        assert table["rows"]["xla"]["tail_gb_s"] == round(
            moved / 1e-3 / 1e9, 1)
        # a row on the sink reads zeros under the kernel: its sum is the
        # number its program started from
        assert [firsts[n] for n in ("kernel_tree", "kernel_16x128",
                                    "kernel_32x128")] == [1.0, 2.0, 3.0]


    def test_the_conv_sweep_of_the_pieces_holds_both_forms(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py conv --pieces`` at a tiny cell, the kernel
        interpreted and the profiler's reading stubbed: ``--parity`` holds
        BOTH forms against the plain convolution of each whole sequence
        over a ragged round and one of short pieces (a hand-over inside a
        slot and inside a block, a frame that would pass the batch's end),
        the tails they leave and every other slot bit for bit; the table
        has the XLA loop beside the kernel under the rule's own tile and
        every (channels, strip) candidate, over eight slots' pieces, one
        slot's chunk and the ragged round, each with its bytes' floor."""
        import functools
        import json

        from deepspeedsyclsupport_tpu.ops import ssm

        monkeypatch.setattr(tune, "CONV_CELLS", {"tiny": dict(
            layers=3, slots=49, rows=12, channels=256, bias=True)})
        monkeypatch.setattr(tune, "CONV_MIXED", {"tiny": dict(
            tokens=96, chunk=16, most=9)})
        monkeypatch.setitem(ssm.CONV_PIECES, "pallas", functools.partial(
            ssm._conv_pieces_pallas, interpret=True))

        def reading(steps, args, carry=None, **_kw):
            for step in steps.values():
                carry, _out = step(carry, *args)
            return {name: {"kernel": 0.5, "xla": 0.25,
                           "calls": {"kernel": 3}}
                    if "kernel" in name else {"xla": 1.5, "calls": {}}
                    for name in steps}

        monkeypatch.setattr(tune, "_traced_kernels", reading)
        tune.conv(["--pieces", "--cell", "tiny", "--parity", "--channels",
                   "128", "--strip", "128"])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        parity = [r for r in out if r["section"] == "conv_pieces_parity"]
        assert [(r["layout"], r["form"]) for r in parity] == [
            ("ragged", "xla"), ("ragged", "pallas"), ("short", "xla"),
            ("short", "pallas")]
        assert all(r["out_err"] < 1e-5 and r["out_max"] > 1
                   and r["tails_differ"] == 0 and r["others_differ"] == 0
                   for r in parity)
        tables = [r for r in out if r["section"] == "conv_pieces"]
        assert [t["layout"] for t in tables] == ["eight_slots", "one_slot",
                                                 "ragged"]
        for t in tables:
            assert not t["failed"] and t["rule"] == {"channels": 256,
                                                     "strip": 256}
            name = t["layout"]
            assert set(t["rows"]) == {
                f"{name}_{form}" for form in (
                    "pieces_xla", "kernel_tree", "kernel_128x128")}
            moved = t["rows_live"] * 256 * 6 + t["pieces"] * 2 * 3 * 256 * 2
            assert t["floor_us_a_piece"] == round(
                1e6 * moved / tune.V5E_HBM / t["pieces"], 2)
            assert t["rows"][f"{name}_kernel_tree"]["us_a_piece"] == round(
                750 / t["pieces"], 2)
            assert t["rows"][f"{name}_pieces_xla"]["ms_a_forward"] == round(
                1500 / t["pieces"] * 9 / 1e3, 3)
        assert (tables[0]["pieces"], tables[1]["pieces"]) == (6, 6)

    def test_the_kda_sweep_runs_both_forms_of_the_pieces(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py kda`` at a tiny cell, the kernels interpreted and
        the profiler's reading stubbed: ``--parity`` holds the state step
        and BOTH forms of the chunked entry against the sequential
        recurrence over five pieces with a hand-over, and the pieces' table
        has the XLA loop beside the kernel, eight slots' pieces and one
        slot's chunk, each with the bytes it has to move."""
        import functools
        import json

        from deepspeedsyclsupport_tpu.ops import kda

        cell = dict(layers=2, slots=19, rows=6, heads=4, dim=16, chunk=8)
        monkeypatch.setattr(tune, "KDA_CELL", cell)
        monkeypatch.setattr(tune, "KDA_PIECES", 2)
        monkeypatch.setattr(tune, "KDA_CHUNK_PIECES", 3)
        step = functools.partial(kda._state_step_pallas, interpret=True)
        monkeypatch.setattr(kda, "_state_step_pallas", step)
        monkeypatch.setitem(kda.STATE_STEPS, "pallas", step)
        monkeypatch.setitem(kda.PIECES, "pallas", functools.partial(
            kda._chunked_pallas, interpret=True))

        def reading(steps, args, carry=None, **_kw):
            for step in steps.values():
                carry, _out = step(carry, *args)
            return {name: {"kernel": 0.5, "xla": 0.25,
                           "calls": {"kernel": 3}}
                    if not name.endswith("xla") else {"xla": 2.0,
                                                      "calls": {}}
                    for name in steps}

        monkeypatch.setattr(tune, "_traced_kernels", reading)
        tune.kda(["--heads", "2", "--piece-heads", "4", "--parity"])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        parity = [r for r in out if r["section"] == "kda_parity"]
        assert [(r["entry"], r.get("form"), r.get("strong_decay"))
                for r in parity] == [
            ("decode_step", None, None), ("chunked", "xla", False),
            ("chunked", "pallas", False), ("chunked", "xla", True),
            ("chunked", "pallas", True)]
        assert all(r["y_err"] < 2e-5 and r["pool_err"] < 2e-5
                   and r["y_max"] > 0.01 for r in parity)
        eight, chunk = [r for r in out if r["section"] == "kda_chunked"]
        state, row = 4 * 16 * 16 * 4, 8 * 4 * 16 * 4
        assert (eight["pieces"], eight["slots"], eight["bytes_moved"]) \
            == (2, 2, 2 * 5 * row + 2 * 2 * state)
        assert (chunk["pieces"], chunk["slots"], chunk["bytes_moved"]) \
            == (3, 1, 3 * 5 * row + 2 * state)
        for table in (eight, chunk):
            assert not table["failed"]
            assert set(table["rows"]) == {"chunked_xla", "kda_piece_4"}
            assert table["rows"]["kda_piece_4"]["ms"] == 0.75
            assert table["rows"]["chunked_xla"]["ms_a_piece"] == round(
                2.0 / table["pieces"], 4)

    def test_the_combine_sweep_runs_both_forms_and_holds_the_sum(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py combine`` at two tiny cells (a held share of the
        experts, and all of them), the profiler's reading stubbed: the
        scatter-add of PRs 26-57, the tree's combine, a checkout's (this
        one) ``combine_rows`` and every candidate for the sort's inverse
        alone are built and run; the tree's is no further from a float64 sum
        than the scatter-add, and every candidate gives the same
        permutation. The cells' shapes come off ``BENCHMARK.json``'s
        configurations: those that serve experts."""
        import json
        import os

        # (a superset: a later cell that serves experts joins without an
        # edit here, as ``glm5-docs-sat`` did in PR 65)
        assert set(tune._combine_cells()) >= {
            "olmoe-chat-sat", "xing4-docs-sat", "dsv2-answers-sat",
            "nemo3-reason-sat", "keye-video-sat", "cmdaplus-rag-sat",
            "solar2-agent-sat", "glm5-docs-sat"}
        assert tune._combine_cells()["glm5-docs-sat"] == dict(
            k=8, d=6144, experts=256, held=16)
        assert tune._combine_cells()["dsv2-answers-sat"] == dict(
            k=6, d=5120, experts=160, held=40)
        monkeypatch.setattr(tune, "_combine_cells", lambda: {
            "share": dict(k=3, d=64, experts=16, held=8),
            "whole": dict(k=2, d=32, experts=4, held=4)})
        ran = []

        def reading(steps, args, **_kw):
            for name, step in steps.items():
                jax.block_until_ready(step(*args))
                ran.append(name)
            return {name: {"xla": 0.5, "calls": {}} for name in steps}

        monkeypatch.setattr(tune, "_traced_kernels", reading)
        root = os.path.join(os.path.dirname(__file__), "..", "..")
        tune.combine(["--rows", "8", "32", "--parent", root])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        assert [(r["cell"], r["t"]) for r in out] == [
            ("share", 8), ("share", 32), ("whole", 8), ("whole", 32)]
        names = {"scatter_add", "tree", "parent"} | {
            f"inv_{n}" for n in tune.COMBINE_INV}
        assert set(ran) == names and len(ran) == 4 * len(names)
        for r in out:
            assert set(r["rows"]) == names and r["inv_differ"] == 0
            assert r["err"]["tree"] <= r["err"]["scatter_add"] < 0.1
            moved = (r["k"] + 1) * r["t"] * r["d"] * 2
            assert r["rows"]["tree"] == {
                "us": 500.0, "gb_s": round(moved / 500.0 / 1e3, 1)}


    def test_the_proj_sweep_runs_both_layouts_of_every_shape(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py proj`` at two tiny cells, the profiler's reading
        stubbed: every distinct ``(in, out)`` of the cells' q, k/v and
        lightning products is built once, with the weights stored
        ``[in, out]`` and ``[out, in]`` (``model.serving_layout``), and the
        two programs of a shape give the same sum. The cells' shapes come
        off ``BENCHMARK.json``'s configurations: every serving cell."""
        import json

        cells = tune._proj_cells()
        assert cells["ouro-reason-sat"] == {"q": (2048, 2048),
                                            "kv": (2048, 2048)}
        assert cells["cmdaplus-rag-sat"] == {"q": (4096, 16384),
                                             "kv": (4096, 1024)}
        assert cells["sala-docs-sat"]["la"] == (4096, 4096)
        monkeypatch.setattr(tune, "_proj_cells", lambda: {
            "square": {"q": (64, 64), "kv": (64, 64)},
            "wide": {"q": (64, 256), "kv": (64, 32), "la": (64, 64)}})

        def reading(steps, args, **_kw):
            for step in steps.values():
                jax.block_until_ready(step(*args))
            return {name: {"xla": 0.6, "calls": {}} for name in steps}

        monkeypatch.setattr(tune, "_traced_kernels", reading)
        tune.proj(["--rows", "16", "48", "--layers", "3", "--passes", "2"])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        assert [(r["d_in"], r["d_out"], r["t"]) for r in out] == [
            (64, 64, 16), (64, 64, 48), (64, 256, 16), (64, 256, 48),
            (64, 32, 16), (64, 32, 48)]
        assert out[0]["cells"] == ["square:q", "square:kv", "wide:la"]
        for r in out:
            assert set(r["rows"]) == set(tune.PROJ_LAYOUTS)
            a, b = (r["rows"][k] for k in ("in_out", "out_in"))
            assert a["us"] == b["us"] == 100.0      # 0.6 ms / (3 x 2)
            assert a["gb_s"] == round(r["d_in"] * r["d_out"] * 2 / 1e5, 1)
            np.testing.assert_allclose(a["sum"], b["sum"], rtol=1e-3,
                                       atol=1e-2)


    def test_the_proj_sweep_runs_the_latent_products(
            self, tune, monkeypatch, capsys):
        """``tpu_tune.py proj`` over latent attention's products: the cells'
        shapes off ``BENCHMARK.json`` (``w_qb``'s and an indexer's ``w_qi``'s
        ``(in, out)``, the two batched products over the parts of ``w_kvb``
        by ``(heads, in, out, the other part's width)``); at a tiny cell,
        the profiler's reading stubbed, the public leaf WHOLE, reshaped and
        cut inside the program, and the part alone, head-major
        (``model.serving_layout``), give the same sum, and the GB/s counts
        the part's bytes."""
        import json

        cells = tune._proj_cells()
        assert cells["dsv2-answers-sat"] == {
            "qb": (1536, 24576), "uk": (128, 128, 512, 128),
            "uv": (128, 512, 128, 128)}
        assert cells["glm5-docs-sat"] == {
            "qb": (2048, 16384), "uk": (64, 192, 512, 256),
            "uv": (64, 512, 256, 192), "qi": (2048, 4096)}
        assert cells["xing4-docs-sat"]["qb"] == (768, 6144)
        assert cells["keye-video-sat"]["qi"] == (2048, 1024)
        monkeypatch.setattr(tune, "_proj_cells", lambda: {
            "latent": {"qb": (32, 96), "uk": (4, 16, 32, 8),
                       "uv": (4, 32, 8, 16), "qi": (32, 64)}})

        def reading(steps, args, **_kw):
            for step in steps.values():
                jax.block_until_ready(step(*args))
            return {name: {"xla": 0.4, "calls": {}} for name in steps}

        monkeypatch.setattr(tune, "_traced_kernels", reading)
        tune.proj(["--rows", "16", "--layers", "2"])
        out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
        assert [(r.get("part"), r.get("heads"), r["d_in"], r["d_out"])
                for r in out] == [(None, None, 32, 96), ("uk", 4, 16, 32),
                                  ("uv", 4, 32, 8), (None, None, 32, 64)]
        for r in out:
            a, b = (r["rows"][k] for k in ("in_out", "out_in"))
            assert a["us"] == b["us"] == 200.0      # 0.4 ms / 2 layers
            assert a["gb_s"] == round(
                r.get("heads", 1) * r["d_in"] * r["d_out"] * 2 / 2e5, 1)
            np.testing.assert_allclose(a["sum"], b["sum"], rtol=1e-3,
                                       atol=1e-2)


class TestSpatialAndTiling:
    """ops/spatial (diffusers fused bias-add family, reference
    csrc/spatial/) and runtime/tiling (reference runtime/zero/tiling.py)."""

    def test_spatial_bias_adds(self):
        from deepspeedsyclsupport_tpu.ops.spatial import (bias_add,
                                                          bias_add_add,
                                                          nhwc_bias_add)

        x = jnp.ones((2, 4, 4, 8))
        b = jnp.arange(8.0)
        np.testing.assert_allclose(np.asarray(bias_add(x, b)),
                                   np.asarray(x + b))
        other = jnp.full_like(x, 2.0)
        np.testing.assert_allclose(np.asarray(bias_add_add(x, b, other)),
                                   np.asarray(x + b + other))
        ob = jnp.ones((8,))
        np.testing.assert_allclose(
            np.asarray(nhwc_bias_add(x, b, other, ob)),
            np.asarray(x + b + other + ob))

    @pytest.mark.parametrize("in_splits,out_splits",
                             [(1, 1), (4, 1), (1, 4), (2, 2)])
    def test_tiled_linear_matches_dense(self, in_splits, out_splits):
        from deepspeedsyclsupport_tpu.runtime.tiling import tiled_linear

        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(k1, (3, 5, 32))
        w = jax.random.normal(k2, (32, 16))
        b = jax.random.normal(k3, (16,))
        want = x @ w + b
        got = tiled_linear(x, w, b, in_splits=in_splits,
                           out_splits=out_splits)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_tiled_linear_grad(self):
        from deepspeedsyclsupport_tpu.runtime.tiling import tiled_linear

        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(k1, (4, 32))
        w = jax.random.normal(k2, (32, 16))
        g1 = jax.grad(lambda w: (tiled_linear(x, w, in_splits=4,
                                              out_splits=2) ** 2).sum())(w)
        g2 = jax.grad(lambda w: ((x @ w) ** 2).sum())(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-5, atol=2e-5)

    def test_tiled_linear_bad_splits(self):
        from deepspeedsyclsupport_tpu.runtime.tiling import tiled_linear

        with pytest.raises(ValueError):
            tiled_linear(jnp.ones((2, 32)), jnp.ones((32, 16)), in_splits=5)


class TestPLDAndEigenvalue:
    """Progressive layer drop (reference runtime/progressive_layer_drop.py)
    and the Hessian power-iteration estimator (runtime/eigenvalue.py)."""

    def test_pld_theta_schedule(self):
        from deepspeedsyclsupport_tpu.runtime.progressive_layer_drop import (
            ProgressiveLayerDrop)

        pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
        assert pld.get_theta() == 1.0
        t0 = pld.update_state(0)
        t100 = pld.update_state(100)
        t_inf = pld.update_state(10_000_000)
        assert t0 == 1.0 and t100 < t0 and abs(t_inf - 0.5) < 1e-6
        assert pld.get_state()["progressive_layer_drop"] is True

    def test_pld_engine_trains_and_drops(self):
        """With theta forced low, PLD must change the loss trajectory (layers
        actually drop) while remaining finite; eval path is unaffected."""
        from deepspeedsyclsupport_tpu.models import build_model

        model = build_model("tiny", dtype="float32")
        cfg = simple_config(progressive_layer_drop={
            "enabled": True, "theta": 0.1, "gamma": 100.0})  # θ ≈ 0.1 fast
        engine, *_ = dstpu.initialize(model=model, config=cfg)
        ids = np.random.RandomState(0).randint(
            0, model.config.vocab_size, (2, 16)).astype(np.int32)
        m = engine.train_batch({"input_ids": ids})   # step 0: θ(0) = 1.0
        assert np.isfinite(float(np.asarray(m["loss"])))
        assert engine.progressive_layer_drop.get_theta() == 1.0
        m = engine.train_batch({"input_ids": ids})   # step 1: θ ≈ 0.1
        assert np.isfinite(float(np.asarray(m["loss"])))
        assert engine.progressive_layer_drop.get_theta() < 0.11

        # dropped-layer forward differs from the full forward
        params = engine.params
        full, _, _ = model._forward(params, jnp.asarray(ids))
        dropped, _, _ = model._forward(
            params, jnp.asarray(ids),
            pld_theta=jnp.float32(0.01), rng=jax.random.PRNGKey(1))
        assert float(np.abs(np.asarray(full - dropped)).max()) > 1e-6

    def test_eigenvalue_quadratic_exact(self):
        """For a quadratic loss ½xᵀAx the Hessian is A — power iteration must
        recover A's top eigenvalue."""
        from deepspeedsyclsupport_tpu.utils.eigenvalue import Eigenvalue

        evals = np.array([5.0, 2.0, 0.5, 0.1], np.float32)
        rng = np.random.RandomState(0)
        Q, _ = np.linalg.qr(rng.randn(4, 4).astype(np.float32))
        A = jnp.asarray(Q @ np.diag(evals) @ Q.T)

        def loss(p, batch):
            x = p["x"]
            return 0.5 * x @ A @ x

        est = Eigenvalue(max_iter=200, tol=1e-5).compute_eigenvalue(
            loss, {"x": jnp.ones((4,))}, None)
        assert abs(est - 5.0) < 1e-2

    def test_eigenvalue_per_block(self):
        from deepspeedsyclsupport_tpu.utils.eigenvalue import Eigenvalue

        def loss(p, batch):
            return 3.0 * jnp.sum(p["a"]["w"] ** 2) + 0.5 * jnp.sum(
                p["b"]["w"] ** 2)

        params = {"a": {"w": jnp.ones((3,))}, "b": {"w": jnp.ones((3,))}}
        out = Eigenvalue(max_iter=100, tol=1e-6).compute_per_block(
            loss, params, None, ["a", "b"])
        assert abs(out["a"] - 6.0) < 1e-3   # Hessian diag = 2·coef
        assert abs(out["b"] - 1.0) < 1e-3


def test_pld_applies_on_unrolled_layer_loop():
    """PLD must engage on the non-scan (unrolled) layer path too."""
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("tiny", dtype="float32", scan_layers=False)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 16)))
    full, _, _ = model._forward(params, ids)
    dropped, _, _ = model._forward(params, ids, pld_theta=jnp.float32(0.01),
                                   rng=jax.random.PRNGKey(1))
    assert float(np.abs(np.asarray(full - dropped)).max()) > 1e-6


def test_pld_rejects_random_ltd_combo():
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("tiny")
    cfg = simple_config(
        progressive_layer_drop={"enabled": True},
        data_efficiency={"enabled": True, "data_routing": {"random_ltd": {
            "enabled": True, "random_ltd_schedule": {
                "min_value": 8, "max_value": 16,
                "schedule_config": {"seq_per_step": 16}}}}})
    with pytest.raises(ValueError):
        dstpu.initialize(model=model, config=cfg)


def test_tiled_linear_module_surface():
    from deepspeedsyclsupport_tpu.runtime.tiling import TiledLinear

    layer = TiledLinear(32, 16, in_splits=2, out_splits=2)
    out = layer(jnp.ones((4, 32)))
    assert out.shape == (4, 16)
    want = jnp.ones((4, 32)) @ layer.weight + layer.bias
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_longformer_index_length_mismatch_rejected():
    from deepspeedsyclsupport_tpu.ops.sparse_attention import (
        BSLongformerSparsityConfig)

    with pytest.raises(ValueError):
        BSLongformerSparsityConfig(4, global_block_indices=[0, 8],
                                   global_block_end_indices=[2])


def test_pld_with_gradient_accumulation():
    """Regression: the injected pld_theta scalar must survive the gas>1
    microbatch reshape (it rides as a (gas,) vector sliced by the scan)."""
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("tiny", dtype="float32")
    cfg = simple_config(progressive_layer_drop={"enabled": True,
                                                "theta": 0.5, "gamma": 0.1},
                        gradient_accumulation_steps=2,
                        train_micro_batch_size_per_gpu=1)
    engine, *_ = dstpu.initialize(model=model, config=cfg)
    ids = np.random.RandomState(0).randint(
        0, model.config.vocab_size,
        (engine.train_batch_size(), 16)).astype(np.int32)
    for _ in range(2):
        m = engine.train_batch({"input_ids": ids})
    assert np.isfinite(float(np.asarray(m["loss"])))


class TestRuntimeUtils:
    """runtime/utils.py parity surface (reference deepspeed/runtime/utils.py
    — the helpers ported user scripts import)."""

    def test_global_norm_and_clipping(self):
        from deepspeedsyclsupport_tpu.runtime.utils import (
            clip_grad_norm_, clip_tensors_by_global_norm,
            get_global_norm, get_global_norm_of_tensors)

        tree = {"a": jnp.full((4,), 3.0), "b": jnp.full((9,), 4.0)}
        n = float(get_global_norm_of_tensors(tree))
        np.testing.assert_allclose(n, np.sqrt(4 * 9 + 9 * 16), rtol=1e-6)
        clipped, norm = clip_grad_norm_(tree, max_norm=1.0)
        assert float(norm) == pytest.approx(n)
        np.testing.assert_allclose(
            float(get_global_norm_of_tensors(clipped)), 1.0, rtol=1e-4)
        # under the cap: untouched
        same, _ = clip_tensors_by_global_norm(tree, max_norm=1e9)
        np.testing.assert_allclose(np.asarray(same["a"]), 3.0)
        assert get_global_norm([3.0, 4.0]) == pytest.approx(5.0)

    def test_inf_norm(self):
        from deepspeedsyclsupport_tpu.runtime.utils import (
            get_global_norm_of_tensors)

        tree = [jnp.array([1.0, -7.0]), jnp.array([2.0])]
        assert float(get_global_norm_of_tensors(
            tree, norm_type=float("inf"))) == 7.0

    def test_misc_helpers(self, tmp_path):
        from deepspeedsyclsupport_tpu.runtime.utils import (
            call_to_str, ensure_directory_exists, get_inactive_params,
            get_only_unique_item, memory_status, see_memory_usage,
            set_random_seed)

        ensure_directory_exists(str(tmp_path / "x" / "y" / "f.txt"))
        assert (tmp_path / "x" / "y").is_dir()
        assert call_to_str("f", 1, b=2) == "f(1, b=2)"
        assert get_only_unique_item([3, 3, 3]) == 3
        with pytest.raises(RuntimeError):
            get_only_unique_item([1, 2])
        set_random_seed(7)
        a = np.random.rand()
        set_random_seed(7)
        assert np.random.rand() == a
        assert get_inactive_params(object()) == []
        see_memory_usage("test", force=True)  # logs, must not raise
        assert isinstance(memory_status("test"), dict)

    def test_partition_reexports(self):
        from deepspeedsyclsupport_tpu.runtime.utils import (
            partition_balanced, partition_uniform)

        assert partition_uniform(8, 4) == [0, 2, 4, 6, 8]
        assert partition_balanced([1, 1, 10, 1], 2)[1] in (2, 3)


class TestJaxProfilerHook:
    def test_trace_brackets_configured_steps(self, tmp_path):
        """{"jax_profiler": ...} captures a device trace around the
        configured step window (reference: NVTX ranges + wall-clock
        breakdown; here a TensorBoard/Perfetto-viewable XLA timeline)."""
        import os

        import deepspeedsyclsupport_tpu as dstpu
        from .simple_model import (SimpleModel, random_dataset,
                                   simple_config)

        model = SimpleModel(hidden_dim=16)
        trace_dir = str(tmp_path / "traces")
        cfg = simple_config(
            train_batch_size=8, train_micro_batch_size_per_gpu=1,
            jax_profiler={"enabled": True, "trace_dir": trace_dir,
                          "start_step": 1, "num_steps": 1})
        engine, _, _, _ = dstpu.initialize(model=model, config=cfg)
        data = random_dataset(8, hidden_dim=16, n_batches=1, seed=0)[0]
        for _ in range(4):
            engine.train_batch(data)
        assert not engine._tracing  # window closed
        # a plugins/profile/<ts>/ dir with trace artifacts exists
        found = []
        for root, _dirs, files in os.walk(trace_dir):
            found.extend(f for f in files if "trace" in f or
                         f.endswith((".pb", ".json.gz", ".xplane.pb")))
        assert found, f"no trace artifacts under {trace_dir}"


def test_tier1_times_reads_the_drivers_junit(tmp_path):
    """``tools/tier1_times.py``: the wall, the sum, the sum by file (a class
    belongs to its file) and the cases of 10 s or more, off a three-case
    junit."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "tier1_times.py")
    spec = importlib.util.spec_from_file_location("tier1_times", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    junit = tmp_path / "t1.xml"
    junit.write_text(
        '<?xml version="1.0" encoding="utf-8"?><testsuites name="pytest">'
        '<testsuite name="pytest" errors="0" failures="0" skipped="0" '
        'tests="3" time="20.5">'
        '<testcase classname="tests.unit.test_a.TestX" name="test_p[1]" '
        'time="12.25" />'
        '<testcase classname="tests.unit.test_a" name="test_q" time="0.75" />'
        '<testcase classname="tests.test_b" name="test_r" time="3.0" />'
        '</testsuite></testsuites>')
    lines = tool.report(str(junit)).splitlines()
    assert lines[0] == ("wall 20 s, sum 16 s, 3 cases, 1 of 10 s or more "
                        "(12 s)")
    assert lines[3].split() == ["13.0", "2", "tests.unit.test_a"]
    assert lines[4].split() == ["3.0", "1", "tests.test_b"]
    assert lines[-1].split() == ["12.2", "tests.unit.test_a.TestX::test_p[1]"]


def test_parity_rows_tells_a_tied_worst_row_from_an_untied_one():
    """``tools/parity_rows.py``'s record of one probe: a row's error is
    ``benchmark.parity``'s (max |served - reference| over the reference's
    std), a row is tied where some layer's router gap is under eps, the
    worst rows come first with their smallest gap, and the checksum of the
    fed-back ids tells two checkouts that compared different sequences."""
    import importlib.util
    import os

    import numpy as np

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "parity_rows.py")
    spec = importlib.util.spec_from_file_location("parity_rows", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rng = np.random.default_rng(0)
    want = rng.normal(size=(6, 32)).astype(np.float32)
    off = np.array([0.01, 0.05, 0.02, 0.04, 0.0, 0.03], np.float32)
    logits = want.copy()
    logits[:, 0] += off * want.std(-1)
    eps = 0.0078125
    gaps = np.full((3, 6), 4 * eps)
    gaps[1, 3] = eps / 2          # the second-worst row has a near-tie
    gaps[2, 4] = eps / 4          # ... and so has the exact row
    ids = np.arange(40)
    rec = tool.rows_record(ids, 34, logits, want, gaps, eps)
    assert (rec["tokens"], rec["prompt"], rec["rows"]) == (40, 34, 6)
    assert rec["rows_with_a_near_tie"] == 2
    np.testing.assert_allclose(
        [rec["err_max"], rec["err_max_untied_rows"],
         rec["err_max_tied_rows"], rec["err_p50_tied_rows"]],
        [0.05, 0.05, 0.04, 0.02], rtol=1e-4)
    assert [(w["row"], w["near_ties"], w["min_gap_over_eps"])
            for w in rec["worst_rows"][:2]] == [(1, 0, 4.0), (3, 1, 0.5)]
    assert rec["min_gap_over_eps_p50"] == 4.0
    other = tool.rows_record(ids[::-1], 34, logits, want, gaps, eps)
    assert other["ids_crc"] != rec["ids_crc"]
    none_tied = tool.rows_record(ids, 34, logits, want, gaps * 100, eps)
    assert none_tied["err_max_tied_rows"] is None
