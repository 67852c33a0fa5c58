"""Quantized collectives + 1-bit optimizer tests (reference analogs:
``tests/unit/ops/quantizer``, ``tests/unit/onebit``, ``tests/unit/runtime/
comm`` compressed-allreduce parity tests)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeedsyclsupport_tpu.comm.quantized import (all_to_all_quant_reduce,
                                                     compressed_allreduce,
                                                     quantized_all_gather)
from deepspeedsyclsupport_tpu.comm.topology import build_topology
from deepspeedsyclsupport_tpu.runtime.onebit import onebit_adam
from deepspeedsyclsupport_tpu.runtime.optimizers import build_optimizer


def _shard_map(topo, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=topo.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _find_eqns(jaxpr, prim_name):
    """Recursively collect eqns of a primitive from a jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == prim_name:
            out.append(eqn)
        for p in ("jaxpr", "call_jaxpr", "branches"):
            v = eqn.params.get(p)
            if v is None:
                continue
            for s in (v if isinstance(v, (list, tuple)) else [v]):
                out.extend(_find_eqns(getattr(s, "jaxpr", s), prim_name))
    return out


class TestQuantizedAllGather:
    def test_matches_fp_gather_within_quant_error(self):
        topo = build_topology(dp=8)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))

        got = _shard_map(topo,
                         partial(quantized_all_gather, axis_name="data",
                                 group_size=64),
                         (P("data", None),), P(None, None))(x)
        # every rank ends with the full array (all-gather of the shards)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x),
                                   atol=0.06, rtol=0)
        # quantization is blockwise: error is bounded by per-block max/127
        err = np.abs(np.asarray(got) - np.asarray(x)).max()
        assert err > 0  # it really did quantize

    def test_int8_on_the_wire(self):
        """The all-gather the collective ACTUALLY issues must carry int8
        payload (the 4× traffic saving) — verified on the traced jaxpr."""
        topo = build_topology(dp=8)
        f = _shard_map(topo,
                       partial(quantized_all_gather, axis_name="data",
                               group_size=64),
                       (P("data", None),), P(None, None))
        jaxpr = jax.make_jaxpr(f)(
            jax.random.normal(jax.random.PRNGKey(1), (8, 64)))
        gathers = _find_eqns(jaxpr.jaxpr, "all_gather")
        assert gathers, "no all_gather issued"
        dtypes = {e.invars[0].aval.dtype for e in gathers}
        assert np.dtype(np.int8) in dtypes
        # no fp gather of the full payload — only the tiny scale array
        fp = [e for e in gathers
              if e.invars[0].aval.dtype == jnp.float32]
        assert all(int(np.prod(e.invars[0].aval.shape)) <= 8 * 64 // 64
                   for e in fp)


class TestQuantReduce:
    def test_matches_reduce_scatter_mean(self):
        topo = build_topology(dp=8)
        # global [8, 64, 32]: each rank holds [8, 64/8=8...] — simpler: feed
        # per-rank chunked input directly inside shard_map
        x = jax.random.normal(jax.random.PRNGKey(2), (64, 32))

        def body(xl):  # xl: [8, 32] local rows = 8 chunks of 1 row
            return all_to_all_quant_reduce(xl, "data", group_size=32)

        got = _shard_map(topo, body, (P("data", None),),
                         P("data", None))(x)
        # reference: mean over the 8 ranks' j-th chunk = mean over groups of rows
        ref = np.asarray(x).reshape(8, 8, 32).mean(axis=0)  # [8, 32]
        np.testing.assert_allclose(np.asarray(got), ref, atol=0.05, rtol=0)


class TestCompressedAllreduce:
    def test_error_feedback_unbiased_over_steps(self):
        """Each call is 1-bit lossy, but with error feedback the running sum of
        outputs tracks the running sum of true means (the 1-bit Adam
        convergence argument)."""
        topo = build_topology(dp=8)
        rng = jax.random.PRNGKey(3)
        grads = jax.random.normal(rng, (20, 8, 128))  # 20 steps, per-rank rows

        def body(gs):
            def step(err, g):
                avg, err = compressed_allreduce(g[0], err, "data")
                return err, avg

            err0 = jnp.zeros((128,))
            _, avgs = lax.scan(step, err0, gs)
            return avgs

        avgs = _shard_map(topo, body, (P(None, "data", None),),
                          P(None, None))(grads)
        true_means = np.asarray(grads).mean(axis=1)  # [20, 128]
        run_err = np.abs(np.cumsum(np.asarray(avgs), 0) -
                         np.cumsum(true_means, 0))
        # cumulative drift stays bounded (error feedback), unlike naive 1-bit
        assert run_err[-1].mean() < run_err.mean() * 4
        naive = np.sign(true_means) * np.abs(true_means).mean(
            axis=-1, keepdims=True)
        naive_err = np.abs(np.cumsum(naive, 0) - np.cumsum(true_means, 0))
        assert run_err[-1].mean() < naive_err[-1].mean()


class TestOneBitAdam:
    def _opt_gap(self, tx, steps=60):
        """Distance from optimum on a quadratic after `steps`."""
        target = jnp.linspace(-1, 1, 16)
        params = jnp.zeros((16,))
        state = tx.init(params)

        @jax.jit
        def step(params, state):
            g = jax.grad(lambda p: jnp.sum((p - target) ** 2))(params)
            up, state = tx.update(g, state, params)
            return optax.apply_updates(params, up), state

        for _ in range(steps):
            params, state = step(params, state)
        return float(jnp.abs(params - target).max())

    def test_converges_like_adam(self):
        gap_1bit = self._opt_gap(onebit_adam(0.05, freeze_step=20))
        gap_adam = self._opt_gap(optax.adam(0.05))
        assert gap_1bit < 0.15
        assert gap_1bit < gap_adam * 3 + 0.05

    def test_long_run_stable(self):
        """300 steps past freeze must keep converging (regression: carrying
        raw local momentum instead of the compressed average diverged)."""
        tx = onebit_adam(0.05, freeze_step=10)
        target = jnp.linspace(-1, 1, 32)
        params = jnp.zeros((32,))
        state = tx.init(params)

        @jax.jit
        def step(params, state):
            g = jax.grad(lambda p: jnp.mean((p - target) ** 2))(params)
            up, state = tx.update(g, state, params)
            return optax.apply_updates(params, up), state

        for _ in range(300):
            params, state = step(params, state)
        assert float(jnp.abs(params - target).max()) < 0.2

    def test_variance_frozen_after_warmup(self):
        tx = onebit_adam(0.1, freeze_step=3)
        params = jnp.ones((4,))
        state = tx.init(params)
        nus = []
        for i in range(6):
            g = jnp.full((4,), float(i + 1))
            _, state = tx.update(g, state, params)
            nus.append(np.asarray(state[0].nu))
        assert not np.allclose(nus[1], nus[2])   # warmup: nu moves
        np.testing.assert_array_equal(nus[3], nus[4])  # frozen
        np.testing.assert_array_equal(nus[4], nus[5])

    def test_registry_builds_onebit_and_jits(self):
        """The registry transform must survive jit (regression:
        inject_hyperparams once traced freeze_step/weight_decay, crashing on
        `if weight_decay:` inside the jitted train step)."""
        tx = build_optimizer("OneBitAdam", {"lr": 1e-3, "freeze_step": 10,
                                            "weight_decay": 0.01})
        params = {"w": jnp.ones((4,))}
        state = tx.init(params)

        @jax.jit
        def step(g, state, params):
            return tx.update(g, state, params)

        up, _ = step({"w": jnp.ones((4,))}, state, params)
        assert up["w"].shape == (4,)

    def test_tuple_pytree_params(self):
        """Tuple-structured param trees must not confuse the compressed-pair
        extraction (regression: is_leaf=tuple misparsed them)."""
        tx = onebit_adam(0.1, freeze_step=1)
        params = (jnp.ones((3,)), jnp.ones((5,)))
        state = tx.init(params)
        g = (jnp.full((3,), 0.5), jnp.full((5,), -0.5))
        for _ in range(3):  # past freeze → compression path active
            up, state = tx.update(g, state, params)
        assert up[0].shape == (3,) and up[1].shape == (5,)

    def test_dp_ranks_stay_synced_through_warmup(self):
        """With axis_name set, replicated params updated per-rank must remain
        IDENTICAL across ranks during warmup (regression: warmup once used
        unsynced local momentum)."""
        topo = build_topology(dp=8)
        tx = onebit_adam(0.05, freeze_step=4, axis_name="data")
        params0 = jnp.zeros((16,))

        def body(gs):  # gs: per-rank grads [1, 16] local
            params = params0
            state = tx.init(params)
            outs = []
            for i in range(8):  # spans warmup (4) and compression stages
                up, state = tx.update(gs[0] * (i + 1), state, params)
                params = optax.apply_updates(params, up)
                outs.append(params)
            return jnp.stack(outs)

        # (one program: eagerly the shard_map walks eight unrolled updates
        # op by op)
        per_rank = jax.jit(_shard_map(topo, body, (P("data", None),),
                                      P("data", None)))(
            jax.random.normal(jax.random.PRNGKey(5), (8, 16)))
        # out_spec P('data') concatenates rank trajectories along dim 0:
        # [8 ranks × 8 steps, 16] → ranks × steps × params, all must be equal
        traj = np.asarray(per_rank).reshape(8, 8, 16)
        for r in range(1, 8):
            np.testing.assert_allclose(traj[r], traj[0], rtol=1e-5, atol=1e-6)


class TestOneBitLamb:
    def _fit(self, opt_type, opt_params=None, steps=40):
        import deepspeedsyclsupport_tpu as dstpu
        from .simple_model import SimpleModel, random_dataset, simple_config

        model = SimpleModel(hidden_dim=32)
        cfg = simple_config(optimizer={
            "type": opt_type,
            "params": {"lr": 1e-2, **(opt_params or {})}})
        engine, _, _, _ = dstpu.initialize(model=model, config=cfg)
        data = random_dataset(engine.train_batch_size(), hidden_dim=32,
                              n_batches=steps)
        return [float(np.asarray(engine.train_batch(b)["loss"]))
                for b in data]

    def test_onebit_lamb_converges_through_freeze(self):
        """Warmup LAMB → freeze transition → compressed-momentum stage, all
        inside one run (reference tests/unit/onebit/test_onebit.py shape)."""
        losses = self._fit("OneBitLamb", {"freeze_step": 10}, steps=60)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.85, losses
        # still improving after the freeze transition
        assert min(losses[12:]) < min(losses[:10]), losses

    def test_zero_one_adam_converges(self):
        losses = self._fit("ZeroOneAdam", {
            "var_freeze_step": 10, "var_update_scaler": 2,
            "local_step_scaler": 4, "local_step_clipper": 4}, steps=40)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.7, losses

    def test_onebit_lamb_state_shapes(self):
        from deepspeedsyclsupport_tpu.runtime.onebit import onebit_lamb

        params = {"w": jnp.ones((8, 8)), "b": jnp.zeros((8,))}
        tx = onebit_lamb(1e-2, freeze_step=2)
        state = tx.init(params)
        # different per-leaf momentum magnitudes → non-trivial scaling coeffs
        g = {"w": jnp.ones((8, 8)), "b": jnp.full((8,), 0.1)}
        for _ in range(4):  # crosses the freeze boundary
            delta, state = tx.update(g, state, params)
            params = optax.apply_updates(params, delta)
        assert int(state.count) == 4
        # scaling coeff was set at the freeze step (no longer the 1.0 init)
        sc = jax.tree_util.tree_leaves(state.scaling_coeff)
        assert any(float(s) != 1.0 for s in sc)

    def test_zero_one_adam_interval_growth(self):
        from deepspeedsyclsupport_tpu.runtime.onebit import zero_one_adam

        params = {"w": jnp.ones((4, 4))}
        tx = zero_one_adam(1e-2, var_freeze_step=100, var_update_scaler=2)
        state = tx.init(params)
        g = jax.tree_util.tree_map(jnp.ones_like, params)
        for _ in range(6):
            _, state = tx.update(g, state, params)
        assert int(state.var_interval) > 1  # exponential policy engaged
