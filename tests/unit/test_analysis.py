"""Static-analysis subsystem tests (``deepspeedsyclsupport_tpu/analysis``).

Three layers:

* graph analyzers against a REAL compiled ZeRO-3 engine step on the 8-device
  virtual mesh — the collective census must match the analytic expectation
  exactly (counts AND bytes), and the fused train step must donate params +
  optimizer state (the bf16 + activation-checkpointing config's contract);
* analyzer unit behavior on small hand-built programs (donation miss, dtype
  upcasts, resharding boundary/internal, jaxpr walker trip counts);
* the codebase lint rule engine + baseline workflow + the ``tools/dslint.py``
  CLI gate that tier-1 runs against the checked-in baseline.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeedsyclsupport_tpu as dstpu
from deepspeedsyclsupport_tpu import analysis as A
from deepspeedsyclsupport_tpu.analysis import baseline as B
from deepspeedsyclsupport_tpu.analysis import codelint
from deepspeedsyclsupport_tpu.analysis.capture import abstract_step_args

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", ".."))


class RectModel:
    """Rectangular single-layer model: ONE fsdp-sharded weight above the
    stage-3 persistence threshold + one small replicated bias, so the
    canonical ZeRO-3 census is exactly predictable (one all-gather of w,
    one grad sync per leaf)."""

    D_IN, D_OUT = 256, 2048

    def init_params(self):
        rng = np.random.default_rng(0)
        return {"w": rng.normal(0, 0.1, (self.D_IN, self.D_OUT))
                .astype(np.float32),
                "b": np.zeros((self.D_OUT,), np.float32)}

    def loss(self, params, batch, rng):
        y = jnp.tanh(batch["x"] @ params["w"] + params["b"])
        return jnp.mean((y - batch["y"]) ** 2)


def _rect_engine(stage=3):
    cfg = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 2,
           "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": stage}, "steps_per_print": 10_000}
    engine, _, _, _ = dstpu.initialize(model=RectModel(), config=cfg)
    rng = np.random.default_rng(1)
    batch = {k: jax.device_put(v, engine.topology.data_sharding(v.ndim))
             for k, v in
             {"x": rng.normal(0, 1, (16, RectModel.D_IN)).astype(np.float32),
              "y": rng.normal(0, 1, (16, RectModel.D_OUT)).astype(np.float32),
              }.items()}
    return engine, batch


# ===================================================================
# collective census: ZeRO-3 expected-vs-observed, exact
# ===================================================================
class TestCollectiveCensus:
    def test_zero3_census_matches_analytic_expectation_exactly(self):
        engine, batch = _rect_engine(stage=3)
        engine.train_batch(batch)
        report = engine.graph_report()

        w_bytes = RectModel.D_IN * RectModel.D_OUT * 4
        b_bytes = RectModel.D_OUT * 4
        exp = A.expected_train_collectives(
            engine.params, engine.topology, 3,
            param_shardings=engine.param_shardings)
        # the analytic formula itself: only w crosses the persistence
        # threshold (fsdp-sharded); every grad leaf syncs across (data,fsdp)
        assert exp.param_gather_count == 1
        assert exp.param_gather_bytes == w_bytes
        assert exp.grad_sync_count == 2
        assert exp.grad_sync_bytes == w_bytes + b_bytes
        assert exp.group_size == 8

        chk = A.check_collectives(report["census"], exp, engine.params,
                                  engine.param_shardings, exact=True)
        assert chk.ok, chk.report()
        # exact observed-side numbers, not just "check passed"
        assert chk.classes.counts()["param_gather"] == 1
        assert chk.classes.bytes_of("param_gather") == w_bytes
        assert chk.classes.bytes_of("grad_sync") == w_bytes + b_bytes
        assert chk.classes.counts()["other"] == 0
        gathers = chk.classes.param_gather
        assert gathers[0]["group_size"] == 8

    def test_stage2_has_no_param_gather_class(self):
        engine, batch = _rect_engine(stage=2)
        engine.train_batch(batch)
        report = engine.graph_report()
        exp = A.expected_train_collectives(
            engine.params, engine.topology, 2,
            param_shardings=engine.param_shardings)
        assert exp.param_gather_count == 0 and exp.param_gather_bytes == 0
        chk = A.check_collectives(report["census"], exp, engine.params,
                                  engine.param_shardings, exact=False)
        assert chk.ok, chk.report()

    def test_graph_report_all_analyzers_ok_on_canonical_step(self):
        engine, batch = _rect_engine(stage=3)
        engine.train_batch(batch)
        report = engine.graph_report()
        for name in ("collectives", "donation", "resharding", "dtype"):
            assert report[name].ok, f"{name}: {report[name].report()}"


# ===================================================================
# donation audit
# ===================================================================
class TestDonationAudit:
    def test_engine_step_donates_params_and_optimizer_state(self):
        engine, batch = _rect_engine(stage=3)
        engine.train_batch(batch)
        rep = engine.graph_report()["donation"]
        assert rep.ok, rep.report()
        # arg0 = params, arg1 = optimizer state: both subtrees aliased
        assert any(p.startswith("arg0") for p in rep.donated)
        assert any(p.startswith("arg1") for p in rep.donated)
        assert rep.wasted_bytes == 0

    def test_bench_train_config_donates(self):
        """The chip training config (bf16 + activation_checkpointing, the
        ROADMAP MFU levers) on the real transformer: params + optimizer
        state must donate — an undonated tree is a silent HBM doubling."""
        from deepspeedsyclsupport_tpu.models import build_model, get_config

        self._run_bench_shaped_donation(build_model, get_config)

    def _run_bench_shaped_donation(self, build_model, get_config):
        cfg = get_config("tiny", remat=True, max_seq_len=64)
        model = build_model(cfg)
        config = {"train_batch_size": 16,
                  "train_micro_batch_size_per_gpu": 2,
                  "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
                  "bf16": {"enabled": True},
                  "activation_checkpointing": {"enabled": True},
                  "steps_per_print": 10_000}
        engine, _, _, _ = dstpu.initialize(model=model, config=config)
        ids = jax.random.randint(jax.random.PRNGKey(0), (16, 64), 0,
                                 cfg.vocab_size)
        batch = {"input_ids": jax.device_put(
            ids, engine.topology.data_sharding(2))}
        engine.train_batch(batch)
        rep = engine.graph_report()["donation"]
        assert rep.ok, rep.report()
        assert any(p.startswith("arg0") for p in rep.donated)
        assert any(p.startswith("arg1") for p in rep.donated)

    def test_missed_donation_is_flagged_with_wasted_bytes(self):
        x = jnp.ones((512, 512), jnp.float32)
        compiled_no = jax.jit(lambda a: a * 2.0).lower(x).compile()
        rep = A.donation_audit(compiled_no, (x,), donate_argnums=(0,))
        assert not rep.ok
        assert len(rep.not_donated) == 1
        assert rep.not_donated[0]["bytes"] == 512 * 512 * 4
        assert rep.wasted_bytes == 512 * 512 * 4

        compiled_yes = jax.jit(lambda a: a * 2.0,
                               donate_argnums=(0,)).lower(x).compile()
        rep = A.donation_audit(compiled_yes, (x,), donate_argnums=(0,))
        assert rep.ok, rep.report()
        assert rep.donated and not rep.not_donated

    def test_pruned_arg_is_moot_not_missed(self):
        """jit prunes unused leaves from the entry computation; a pruned
        donatable leaf has no buffer to double and must not be blamed."""
        x = jnp.ones((256, 256), jnp.float32)
        unused = jnp.ones((128, 128), jnp.float32)
        compiled = jax.jit(lambda a, u: a + 1.0,
                           donate_argnums=(0, 1)).lower(x, unused).compile()
        rep = A.donation_audit(compiled, (x, unused), donate_argnums=(0, 1))
        assert rep.ok, rep.report()

    def test_parse_aliased_params(self):
        from deepspeedsyclsupport_tpu.analysis.donation import \
            parse_aliased_params
        text = ("input_output_alias={ {0}: (0, {}, may-alias), "
                "{1}: (2, {}, may-alias) }")
        assert parse_aliased_params(text) == [0, 2]
        assert parse_aliased_params("no alias header here") == []


# ===================================================================
# dtype audit
# ===================================================================
class TestDtypeAudit:
    def test_activation_upcast_flagged_param_upcast_sanctioned(self):
        def f(x, w):
            h = (x @ w).astype(jnp.float32)        # activation upcast: BAD
            g = w.astype(jnp.float32)              # master-weight: sanctioned
            return h.sum() + g.sum()

        x = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
        rep = A.dtype_audit(f, x, w, allowed_shapes=[(256, 256)])
        assert not rep.ok
        assert len(rep.upcasts) == 1
        assert rep.upcasts[0]["shape"] == (64, 256)
        assert rep.sanctioned >= 1

    def test_clean_bf16_graph_passes(self):
        def f(x, w):
            # elementwise + max reduction stay in bf16 (jnp.sum's f32
            # accumulator IS an activation upcast and would correctly
            # be flagged — see the next test)
            return jnp.tanh(x @ w).max()

        x = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
        rep = A.dtype_audit(f, x, w)
        assert rep.ok, rep.report()

    def test_default_sum_accumulator_upcast_is_flagged(self):
        rep = A.dtype_audit(lambda x, w: (x @ w).sum(),
                            jax.ShapeDtypeStruct((64, 256), jnp.bfloat16),
                            jax.ShapeDtypeStruct((256, 256), jnp.bfloat16))
        assert not rep.ok and rep.upcasts[0]["shape"] == (64, 256)

    def test_small_upcasts_below_floor_ignored(self):
        def f(x):
            return x.astype(jnp.float32).sum()     # 64 elements: noise

        rep = A.dtype_audit(f, jax.ShapeDtypeStruct((64,), jnp.bfloat16))
        assert rep.ok

    def test_scan_body_upcast_multiplied_by_trip_count(self):
        def f(xs):
            def body(c, x):
                return c + x.astype(jnp.float32).sum(), ()
            return jax.lax.scan(body, jnp.float32(0), xs)[0]

        xs = jax.ShapeDtypeStruct((4, 64, 256), jnp.bfloat16)
        rep = A.dtype_audit(f, xs)
        assert not rep.ok
        (u,) = rep.upcasts
        assert u["mult"] == 4
        assert u["bytes"] == 64 * 256 * 2 * 4


# ===================================================================
# resharding audit
# ===================================================================
class TestReshardingAudit:
    def test_boundary_mismatch_detected(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        s_x = NamedSharding(mesh, PartitionSpec("x"))
        s_rep = NamedSharding(mesh, PartitionSpec())
        aval = jax.ShapeDtypeStruct((16, 4), jnp.float32, sharding=s_x)
        compiled = jax.jit(lambda a: a * 2.0).lower(aval).compile()

        ok = A.resharding_audit(compiled, given_in_shardings=[s_x])
        assert ok.ok, ok.report()
        bad = A.resharding_audit(compiled, given_in_shardings=[s_rep])
        assert not bad.ok
        assert bad.boundary_mismatches[0]["index"] == 0

    def test_internal_reshard_spellings_are_suspects(self):
        census = [
            {"op": "all-to-all", "bytes": 4096, "shape": "f32[8,128]",
             "group_size": 8},
            {"op": "collective-permute", "bytes": 2048, "shape": "f32[8,64]",
             "group_size": 8},
        ]
        rep = A.resharding_audit("unused-hlo-text", census=census)
        assert not rep.ok
        assert len(rep.internal_suspects) == 2
        assert rep.suspect_bytes == 4096 + 2048


# ===================================================================
# jaxpr walker (shared with the flops profiler)
# ===================================================================
class TestJaxprWalk:
    def test_scan_multiplies_flops_by_trip_count(self):
        from deepspeedsyclsupport_tpu.profiling.flops_profiler import \
            count_jaxpr_flops

        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        x1 = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        xs = jax.ShapeDtypeStruct((5, 8, 64), jnp.float32)

        def single(w, x):
            return (x @ w).sum()

        def scanned(w, xs):
            def body(c, x):
                return c + (x @ w).sum(), ()
            return jax.lax.scan(body, jnp.float32(0), xs)[0]

        f1 = count_jaxpr_flops(jax.make_jaxpr(single)(w, x1).jaxpr)
        fs = count_jaxpr_flops(jax.make_jaxpr(scanned)(w, xs).jaxpr)
        assert fs["dot_general"] == 5 * f1["dot_general"]

    def test_cond_walks_every_branch(self):
        # branch order in eqn.params['branches'] is lowering-defined (for
        # lax.cond index 0 is the FALSE branch), so the walker descends
        # into ALL branches — an over-approximation, which is the safe
        # direction for audits
        from deepspeedsyclsupport_tpu.analysis.jaxpr_walk import iter_eqns

        def f(pred, x):
            return jax.lax.cond(pred, lambda a: a + 1.0, lambda a: a - 1.0, x)

        jaxpr = jax.make_jaxpr(f)(True, jnp.ones((4,))).jaxpr
        names = sorted(e.primitive.name for e, _ in iter_eqns(jaxpr)
                       if e.primitive.name in ("add", "sub"))
        assert names == ["add", "sub"]


# ===================================================================
# codebase lint rules
# ===================================================================
def _lint_file(tmp_path, relpath, source, rules=None):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    return codelint.lint_paths(str(tmp_path), relpaths=[relpath],
                               rules=rules)


class TestSignalHandlerSafety:
    RULE = [codelint.SignalHandlerSafety()]

    def test_logging_in_registered_handler_flagged(self, tmp_path):
        src = ("import signal, logging\n"
               "def handler(signum, frame):\n"
               "    logging.warning('dying %d', signum)\n"
               "def install():\n"
               "    signal.signal(signal.SIGTERM, handler)\n")
        vs = _lint_file(tmp_path, "launcher/x.py", src, self.RULE)
        assert any(v.rule == "signal-handler-safety" for v in vs)

    def test_store_only_handler_clean(self, tmp_path):
        src = ("import signal\n"
               "class S:\n"
               "    pass\n"
               "STATE = S()\n"
               "def _on_signal(signum, frame):\n"
               "    STATE.flag = signum\n"
               "def install():\n"
               "    signal.signal(signal.SIGTERM, _on_signal)\n")
        assert _lint_file(tmp_path, "launcher/x.py", src, self.RULE) == []

    def test_lock_and_raise_flagged(self, tmp_path):
        src = ("import signal\n"
               "import threading\n"
               "L = threading.Lock()\n"
               "def _on_signal(signum, frame):\n"
               "    with L:\n"
               "        raise SystemExit(1)\n")
        vs = _lint_file(tmp_path, "x.py", src, self.RULE)
        kinds = {v.message.split(";")[0] for v in vs}
        assert len(vs) >= 2  # the with-block and the raise


class TestWallClockRule:
    RULE = [codelint.WallClockInStepPath()]

    def test_flagged_in_step_path(self, tmp_path):
        src = "import time\ndef step():\n    t0 = time.time()\n"
        vs = _lint_file(tmp_path, "runtime/zero.py", src, self.RULE)
        assert [v.rule for v in vs] == ["wall-clock-in-step-path"]

    def test_ignored_off_step_path(self, tmp_path):
        src = "import time\ndef step():\n    t0 = time.time()\n"
        assert _lint_file(tmp_path, "utils/other.py", src, self.RULE) == []

    def test_suppression_comment(self, tmp_path):
        src = ("import time\n"
               "def stamp():\n"
               "    # human-facing wall timestamp, not a duration\n"
               "    return time.time()  "
               "# dslint: allow(wall-clock-in-step-path)\n")
        assert _lint_file(tmp_path, "runtime/zero.py", src, self.RULE) == []


class TestHostSyncRule:
    RULE = [codelint.HostSyncInStepPath()]

    def test_flagged_in_hot_function(self, tmp_path):
        src = ("import jax\n"
               "def hot_loop(x):\n"
               "    return jax.block_until_ready(x)\n")
        vs = _lint_file(tmp_path, "runtime/zero.py", src, self.RULE)
        assert [v.rule for v in vs] == ["host-sync-in-step-path"]
        assert "hot_loop" in vs[0].message

    def test_sanctioned_site_clean(self, tmp_path):
        src = ("import jax\n"
               "def barrier(x):\n"
               "    return jax.block_until_ready(x)\n")
        assert _lint_file(tmp_path, "comm/comm.py", src, self.RULE) == []


class TestEventNameRule:
    def test_undeclared_name_in_declared_group_flagged(self, tmp_path):
        src = "def f(m):\n    m.write_events([('Goodput/typo_xyz', 1, 0)])\n"
        vs = _lint_file(tmp_path, "runtime/x.py", src,
                        [codelint.UndeclaredEventName()])
        assert [v.rule for v in vs] == ["undeclared-event-name"]

    def test_declared_and_prefix_names_clean(self, tmp_path):
        src = ("def f(m):\n"
               "    m.write_events([('Goodput/compile_s', 1, 0)])\n"
               "    m.write_events([('Comm/anything_goes', 1, 0)])\n"
               "    base = 'Comm/'\n")
        assert _lint_file(tmp_path, "runtime/x.py", src,
                          [codelint.UndeclaredEventName()]) == []

    def test_foreign_groups_and_tests_ignored(self, tmp_path):
        src = "p = 'some/file/path.py'\nq = 'Goodput/typo'\n"
        assert _lint_file(tmp_path, "tests/unit/x.py", src,
                          [codelint.UndeclaredEventName()]) == []
        vs = _lint_file(tmp_path, "runtime/x.py",
                        "p = 'some/file/path.py'\n",
                        [codelint.UndeclaredEventName()])
        assert vs == []


# ===================================================================
# baseline workflow
# ===================================================================
def _v(rule, path, snippet, line=1):
    return codelint.Violation(rule, path, line, "msg", snippet)


class TestBaseline:
    def test_round_trip_and_check(self, tmp_path):
        bl_path = str(tmp_path / "bl.json")
        old = [_v("r", "a.py", "x = 1"), _v("r", "a.py", "x = 1", line=9),
               _v("r", "b.py", "y = 2")]
        B.save_baseline(bl_path, old)
        baseline = B.load_baseline(bl_path)
        assert baseline == {"r|a.py|x = 1": 2, "r|b.py|y = 2": 1}

        # same debt, one entry fixed, one NEW violation
        now = [_v("r", "a.py", "x = 1", line=30),   # moved: same key
               _v("r", "a.py", "x = 1", line=41),
               _v("r", "c.py", "z = 3")]            # new
        chk = B.check_against_baseline(now, baseline)
        assert not chk.ok
        assert [v.path for v in chk.new] == ["c.py"]
        assert len(chk.baselined) == 2
        assert chk.stale_keys == ["r|b.py|y = 2"]

    def test_count_growth_is_new(self):
        baseline = {"r|a.py|x = 1": 1}
        now = [_v("r", "a.py", "x = 1"), _v("r", "a.py", "x = 1", line=7)]
        chk = B.check_against_baseline(now, baseline)
        assert len(chk.new) == 1 and len(chk.baselined) == 1

    def test_missing_baseline_is_empty(self, tmp_path):
        assert B.load_baseline(str(tmp_path / "nope.json")) == {}

    def test_version_mismatch_raises(self, tmp_path):
        p = tmp_path / "bl.json"
        p.write_text(json.dumps({"version": 99, "violations": {}}))
        with pytest.raises(ValueError):
            B.load_baseline(str(p))


# ===================================================================
# the tier-1 CLI gate
# ===================================================================
class TestDslintCLI:
    def test_check_passes_on_tree(self):
        """THE tier-1 gate: no new violations vs the checked-in baseline."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "dslint.py"),
             "--check"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=300)
        assert r.returncode == 0, f"dslint --check failed:\n{r.stdout}\n{r.stderr}"
        assert "0 new" in r.stdout

    def test_list_rules(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "dslint.py"),
             "--list-rules"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=300)
        assert r.returncode == 0
        for rule in ("signal-handler-safety", "undeclared-event-name",
                     "wall-clock-in-step-path", "host-sync-in-step-path"):
            assert rule in r.stdout

    def test_live_tree_lint_matches_baseline_file(self):
        """In-process equivalent of --check (no subprocess): the committed
        baseline must contain every currently-firing violation."""
        violations = codelint.lint_paths(REPO_ROOT)
        baseline = B.load_baseline(os.path.join(REPO_ROOT, "tools",
                                                "dslint_baseline.json"))
        chk = B.check_against_baseline(violations, baseline)
        assert chk.ok, "NEW violations:\n" + "\n".join(map(str, chk.new))


# ===================================================================
# shared capture helper (satellite: engine aval dedupe)
# ===================================================================
class TestCapture:
    def test_abstract_step_args_keeps_mesh_shardings(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        s = NamedSharding(mesh, PartitionSpec("x"))
        arr = jax.device_put(np.zeros((16, 4), np.float32), s)
        tree = {"a": arr, "b": np.float32(3.0)}
        avals = abstract_step_args(tree)
        assert avals["a"].shape == (16, 4)
        assert avals["a"].sharding == s
        assert avals["b"].shape == ()
