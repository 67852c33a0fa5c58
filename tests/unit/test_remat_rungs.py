"""What a checkpointed layer keeps for its backward pass (``models/remat.py``):
the chooser's arithmetic, the rungs' mathematics and what each leaves to
recompute, and the engine's side (no policy: by room; a policy: as given).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeedsyclsupport_tpu as dstpu
from deepspeedsyclsupport_tpu.accelerator import get_accelerator
from deepspeedsyclsupport_tpu.models import build_model, remat
from deepspeedsyclsupport_tpu.models.config import get_config
from deepspeedsyclsupport_tpu.monitor import mfu

GIB = 2**30
V5E = 15.75 * GIB  # memory_stats()["bytes_limit"] of one TPU v5e


def _n_params(cfg):
    shapes = jax.eval_shape(build_model(cfg).init_params)
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


def _choice(layers, tokens, fsdp, limit):
    """The rung the arithmetic takes for mistral-7b's widths at ``layers``
    and ``tokens`` a device, under float32 params, gradients and two Adam
    moments (16 B a parameter) sharded ``fsdp`` ways, as the engine and the
    model compute it between them."""
    cfg = get_config("mistral-7b", num_layers=layers)
    free = None if limit is None else \
        int(limit * (1 - remat.MARGIN)) - _n_params(cfg) * 16 // fsdp
    return remat.choose_rung(cfg, tokens, free)


# the benchmark's two training cells: mistral-7b-d2 (batch 4 x 2,048 on one
# chip) and mistral-7b-zero3 (batch 8 x 2,048 over four)
CELLS = {"train-1chip": (2, 8192, 1), "zero3-4chip": (6, 4096, 4)}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("limit,rung", [
    (V5E, "attn+mlp"), (None, "nothing_saveable")])
def test_the_cells_take_the_richest_rung_on_a_v5e_and_none_without_a_limit(
        cell, limit, rung):
    assert _choice(*CELLS[cell], limit) == rung


@pytest.mark.parametrize("limit_gib,rung", [
    (15.75, "attn+mlp"), (14.0, "attn"), (13.5, "nothing_saveable"),
    (8.0, "nothing_saveable")])
def test_a_smaller_device_takes_a_leaner_rung(limit_gib, rung):
    assert _choice(*CELLS["train-1chip"], limit_gib * GIB) == rung


def test_rung_bytes_at_the_cells_widths():
    cfg = get_config("mistral-7b", num_layers=2)
    b = remat.rung_bytes(cfg, 8192)
    # q, k, v + o + lse + the sublayer's output; + gate and up
    attn = 8192 * ((4096 + 2 * 1024 + 4096 + 4096) * 2 + 32 * 4)
    assert b == {"attn+mlp": attn + 8192 * 2 * 14336 * 2, "attn": attn,
                 "nothing_saveable": 0}
    # heads and projections split over a model axis, the stream does not
    tp = remat.rung_bytes(cfg, 8192, model_shards=4)
    assert tp["attn"] == (attn - 8192 * 4096 * 2) // 4 + 8192 * 4096 * 2


def test_choose_rung_is_monotone_in_what_is_free():
    cfg = get_config("tiny", num_layers=2)
    per_layer = remat.rung_bytes(cfg, 256)
    reserve = remat.working_bytes(cfg, 256, per_layer["attn+mlp"])
    need = {r: 2 * n + reserve for r, n in per_layer.items()}
    taken = [remat.choose_rung(cfg, 256, free) for free in (
        0, need["attn"] - 1, need["attn"], need["attn+mlp"] - 1,
        need["attn+mlp"], 10**12)]
    assert taken == ["nothing_saveable"] * 2 + ["attn"] * 2 + ["attn+mlp"] * 2
    assert remat.choose_rung(cfg, 256, None) == "nothing_saveable"


# ------------------------------------------------------------ the mathematics
def _tiny(rung, attn_impl="flash", **kw):
    return build_model("tiny", num_layers=2, remat=True, remat_policy=rung,
                       attn_impl=attn_impl, **kw)


def _batch(model):
    ids = jnp.arange(2 * 128).reshape(2, 128) * 7 % model.config.vocab_size
    return {"input_ids": ids}


@pytest.mark.parametrize("attn_impl", ["flash", "xla"])
def test_loss_and_gradients_are_equal_across_the_rungs(attn_impl):
    out = {}
    for rung in remat.RUNGS:
        m = _tiny(rung, attn_impl)
        out[rung] = jax.value_and_grad(
            lambda p: m.loss(p, _batch(m))[0])(m.init_params())
        assert m.remat_choice["rung"] == rung
    loss0, grads0 = out["nothing_saveable"]
    for rung in ("attn+mlp", "attn"):
        loss, grads = out[rung]
        assert float(loss) == float(loss0)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            grads, grads0)


def test_moe_and_two_matrix_mlps_carry_the_names_too():
    for kw in ({"num_experts": 4, "num_experts_per_tok": 2},
               {"mlp_type": "mlp", "activation": "gelu"}):
        got = {}
        for rung in ("attn+mlp", "nothing_saveable"):
            m = _tiny(rung, **kw)
            got[rung] = jax.grad(lambda p: m.loss(p, _batch(m))[0])(
                m.init_params())
            # the experts' (or the two-matrix MLP's) products, told from
            # the dispatch and combine products by their width
            assert (m.config.intermediate_size in _recomputed(m)["dots"]) \
                == (rung == "nothing_saveable"), kw
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            got["attn+mlp"], got["nothing_saveable"])


def _recomputed(model):
    """What the backward pass recomputes: walks the gradient's jaxpr and
    looks inside the differentiated ``jax.checkpoint`` regions for matmuls
    of the named products' widths and for a kernel that runs forward."""
    cfg = model.config
    widths = {cfg.q_dim, cfg.kv_dim, cfg.intermediate_size,
              cfg.moe_intermediate_size or cfg.intermediate_size}
    found = {"dots": set(), "kernels": 0, "regions": 0}

    def walk(jaxpr, inside):
        for e in jaxpr.eqns:
            region = e.primitive.name == "remat2" and \
                e.params.get("differentiated")
            found["regions"] += bool(region)
            if inside and e.primitive.name == "dot_general" and \
                    e.outvars[0].aval.shape[-1] in widths and \
                    "rematted_computation" in str(e.source_info.name_stack):
                found["dots"].add(e.outvars[0].aval.shape[-1])
            if inside and e.primitive.name == "pallas_call" and \
                    "rematted_computation" in str(e.source_info.name_stack):
                found["kernels"] += 1
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, inside or region)

    walk(jax.make_jaxpr(jax.grad(lambda p: model.loss(p, _batch(model))[0]))(
        model.init_params()).jaxpr, False)
    assert found["regions"], "no differentiated checkpoint region found"
    return found


@pytest.mark.parametrize("rung,dots,kernels", [
    ("attn+mlp", False, 0),     # nothing but elementwise is recomputed
    ("attn", True, 0),          # the MLP's gate and up products are
    ("nothing_saveable", True, 1)])  # and q, k, v and the flash forward
def test_what_the_backward_recomputes_under_each_rung(rung, dots, kernels):
    found = _recomputed(_tiny(rung))
    assert (bool(found["dots"]), found["kernels"]) == (dots, kernels)


def test_the_pipelined_and_random_ltd_stacks_take_no_rung():
    m = build_model("tiny", num_layers=4, remat=True, remat_policy="attn+mlp",
                    random_ltd=True, random_ltd_current=64, attn_impl="xla")
    jax.make_jaxpr(jax.grad(lambda p: m.loss(p, _batch(m))[0]))(
        m.init_params())
    assert getattr(m, "remat_choice", None) is None


# ------------------------------------------------------------------ the engine
def _engine(ac, limit=None, monkeypatch=None, layers=2):
    if limit is not None:
        monkeypatch.setattr(type(get_accelerator()), "total_memory",
                            lambda self, device=None: int(limit))
    model = build_model("tiny", num_layers=layers)
    topo = dstpu.build_topology(dp=1, fsdp=4, devices=jax.devices()[:4])
    cfg = {"train_batch_size": 8, "bf16": {"enabled": True},
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "activation_checkpointing": ac,
           "zero_optimization": {"stage": 3}, "steps_per_print": 10**9}
    engine, *_ = dstpu.initialize(model=model, config=cfg, topology=topo)
    ids = jnp.arange(8 * 128).reshape(8, 128) * 7 % model.config.vocab_size
    return engine, {"input_ids": ids}


def test_without_a_limit_the_engine_keeps_nothing_and_says_so():
    engine, batch = _engine({})
    assert engine.module.config.remat_policy == "auto"
    assert engine.module.config.remat_free_bytes is None
    engine.train_batch(batch)
    assert engine.remat_choice == {"rung": "nothing_saveable",
                                   "saved_bytes": 0, "auto": True}
    engine.compiled_train_step()
    assert mfu.step_record("train_batch_fn")["remat"] == engine.remat_choice


@pytest.fixture(scope="module")
def loss_under_an_explicit_policy():
    engine, batch = _engine({"policy": "nothing_saveable"})
    return float(engine.train_batch(batch)["loss"])


@pytest.mark.parametrize("limit,rung", [
    (1 << 30, "attn+mlp"), (2_400_000, "attn+mlp"), (2_200_000, "attn"),
    (1_900_000, "nothing_saveable")])
def test_with_a_limit_the_engine_takes_the_rung_that_fits(
        monkeypatch, limit, rung, loss_under_an_explicit_policy):
    engine, batch = _engine({}, limit, monkeypatch)
    free = engine.module.config.remat_free_bytes
    # a quarter of the tiny model's 16 B a parameter lives on each device
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(engine.params))
    assert free == pytest.approx(limit * 0.95 - n * 16 / 4, rel=0.02)
    loss = float(engine.train_batch(batch)["loss"])
    assert engine.remat_choice["rung"] == rung
    assert engine.remat_choice["saved_bytes"] == 2 * remat.rung_bytes(
        engine.module.config, 8 * 128 / 4)[rung]
    assert loss == loss_under_an_explicit_policy


@pytest.mark.parametrize("ac,policy", [
    ({"policy": "nothing_saveable"}, "nothing_saveable"),
    ({"policy": "dots_saveable"}, "dots_saveable"),
    ({"cpu_checkpointing": True}, "offload_dots_to_host")])
def test_an_explicit_policy_is_passed_through_untouched(
        monkeypatch, ac, policy):
    engine, _ = _engine(ac, 1 << 30, monkeypatch)
    assert engine.module.config.remat_policy == policy
    assert engine.module.config.remat_free_bytes is None
    assert engine.remat_choice is None


def test_a_step_that_does_not_fit_falls_back_one_rung(monkeypatch):
    engine, batch = _engine({}, 1 << 30, monkeypatch)
    engine._train_batch_fn = engine._build_train_batch_fn()
    real, calls = engine._train_batch_fn, []

    def too_big(*args):
        calls.append(engine.module.config.remat_policy)
        real.lower(*args)           # traces: the model notes its choice
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm.")

    engine._train_batch_fn = too_big
    loss = float(engine.train_batch(batch)["loss"])
    assert calls == ["auto"]
    assert engine.remat_choice == {
        "rung": "attn", "auto": True, "saved_bytes": 2 * remat.rung_bytes(
            engine.module.config, 8 * 128 / 4)["attn"]}
    assert np.isfinite(loss)
    # another fault is not this mechanism's to catch
    engine._train_batch_fn = lambda *a: (_ for _ in ()).throw(
        jax.errors.JaxRuntimeError("INTERNAL: something else"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
        engine.train_batch(batch)
