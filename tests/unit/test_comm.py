"""Collectives façade tests (reference: ``tests/unit/comm/test_dist.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeedsyclsupport_tpu.comm as dist
from deepspeedsyclsupport_tpu.comm.comms_logging import comms_logger
from deepspeedsyclsupport_tpu.comm.topology import build_topology


@pytest.fixture
def topo():
    return build_topology(dp=-1)


def _smap(topo, fn, in_spec, out_spec):
    return shard_map(fn, mesh=topo.mesh, in_specs=in_spec, out_specs=out_spec,
                     check_vma=False)


def test_all_reduce_sum(topo):
    x = jnp.arange(8.0)
    out = _smap(topo, lambda v: dist.all_reduce(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_all_reduce_ops(topo):
    x = jnp.arange(8.0)
    mx = _smap(topo, lambda v: dist.all_reduce(v, "data", op="max"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(mx), np.full(8, 7.0))
    mean = _smap(topo, lambda v: dist.pmean(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(mean), np.full(8, 3.5))


def test_all_gather(topo):
    x = jnp.arange(8.0)
    out = _smap(topo, lambda v: dist.all_gather(v, "data"), P("data"), P(None))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_reduce_scatter(topo):
    # every shard holds [0..7]; reduce-scatter sums and hands shard i element i*8
    x = jnp.tile(jnp.arange(8.0), (8,))
    out = _smap(topo, lambda v: dist.reduce_scatter(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 8)


def test_all_to_all(topo):
    x = jnp.arange(64.0).reshape(8, 8)

    def body(v):  # v: (1, 8) per device → (8, 1): device i ends with column i
        return dist.all_to_all(v, "data", split_axis=1, concat_axis=0)

    out = _smap(topo, body, P("data", None), P("data", None))(x)
    # stacking each device's column along dim0 yields x.T flattened column-major
    np.testing.assert_allclose(
        np.asarray(out), np.arange(64.0).reshape(8, 8).T.reshape(64, 1))


def test_ppermute_ring(topo):
    x = jnp.arange(8.0)
    out = _smap(topo, lambda v: dist.send_recv_next(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))
    out = _smap(topo, lambda v: dist.send_recv_prev(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), -1))


def test_broadcast(topo):
    x = jnp.arange(8.0)
    out = _smap(topo, lambda v: dist.broadcast(v, "data", src=3), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_kill_switch(topo, monkeypatch):
    monkeypatch.setenv("DSTPU_COMM_ALL_REDUCE_OFF", "1")
    x = jnp.arange(8.0)
    out = _smap(topo, lambda v: dist.all_reduce(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))  # identity


def test_comms_logger_records(topo):
    comms_logger.reset()
    comms_logger.configure(enabled=True)
    x = jnp.arange(8.0, dtype=jnp.float32)
    jax.jit(_smap(topo, lambda v: dist.all_reduce(v, "data"), P("data"), P("data")))(x)
    snap = comms_logger.snapshot()
    comms_logger.configure(enabled=False)
    assert "all_reduce[data]" in snap
    assert snap["all_reduce[data]"]["count"] >= 1
    assert snap["all_reduce[data]"]["total_bytes"] == 4  # per-shard bytes at trace
    table = comms_logger.log_summary()
    assert "all_reduce" in table


def test_init_distributed_single_host():
    assert dist.init_distributed() is False
    assert dist.is_initialized()
    dist.barrier()
    assert dist.get_world_size() == 1  # process-level (single controller)
    assert dist.get_device_count() == 8
    assert dist.get_rank() == 0


def test_broadcast_masks_nan_garbage(topo):
    """Non-src shards holding NaN (uninitialized params) must not poison broadcast."""
    x = jnp.where(jnp.arange(8.0) == 3, 42.0, jnp.nan)
    out = _smap(topo, lambda v: dist.broadcast(v, "data", src=3), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 42.0))


def test_shift_no_wrap(topo):
    x = jnp.arange(1.0, 9.0)
    out = _smap(topo, lambda v: dist.send_recv_next(v, "data", wrap=False),
                P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), [0., 1., 2., 3., 4., 5., 6., 7.])
    out = _smap(topo, lambda v: dist.send_recv_prev(v, "data", wrap=False),
                P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), [2., 3., 4., 5., 6., 7., 8., 0.])


class TestHierarchicalAllToAll:
    """Two-hop a2a (reference utils/groups.py:356 hierarchical MoE groups):
    must be bit-equivalent to the flat all_to_all for every group size."""

    @pytest.mark.parametrize("group_size", [1, 2, 4, 8])
    def test_matches_flat_all_to_all(self, mesh8, group_size):
        import jax
        from jax.sharding import PartitionSpec as P

        import deepspeedsyclsupport_tpu.comm as dist

        topo = mesh8
        x = jnp.arange(8 * 16 * 4, dtype=jnp.float32).reshape(8, 16, 4)

        def flat(v):
            return dist.all_to_all(v, "data", split_axis=1, concat_axis=0)

        def hier(v):
            return dist.hierarchical_all_to_all(v, "data", group_size,
                                                split_axis=1, concat_axis=0)

        kw = dict(mesh=topo.mesh, in_specs=P("data"), out_specs=P("data"),
                  check_vma=False)
        a = shard_map(flat, **kw)(x)
        b = shard_map(hier, **kw)(x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_same_axes_roundtrip(self, mesh8):
        """a2a then inverse a2a over (split,concat) swapped returns input."""
        import jax
        from jax.sharding import PartitionSpec as P

        import deepspeedsyclsupport_tpu.comm as dist

        topo = mesh8
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 8, 4))

        def rt(v):
            y = dist.hierarchical_all_to_all(v, "data", 4, split_axis=1,
                                             concat_axis=0)
            return dist.hierarchical_all_to_all(y, "data", 4, split_axis=0,
                                                concat_axis=1)

        out = shard_map(rt, mesh=topo.mesh, in_specs=P("data"),
                            out_specs=P("data"), check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   rtol=1e-6)

    def test_indivisible_group_rejected(self, mesh8):
        import jax
        from jax.sharding import PartitionSpec as P

        import deepspeedsyclsupport_tpu.comm as dist

        topo = mesh8
        x = jnp.ones((8, 8))
        with pytest.raises(ValueError):
            shard_map(
                lambda v: dist.hierarchical_all_to_all(v, "data", 3,
                                                       split_axis=1),
                mesh=topo.mesh, in_specs=P("data"), out_specs=P("data"),
                check_vma=False)(x)


class TestReferenceSurfaceParity:
    """Root-based ops, p2p, coalesced variants and aliases (reference
    comm/comm.py public API) under the 8-device sim mesh."""

    def _run(self, fn, x, n=8):
        import deepspeedsyclsupport_tpu as ds
        from jax.sharding import PartitionSpec as P

        topo = ds.build_topology(dp=n)
        return np.asarray(jax.jit(shard_map(
            fn, mesh=topo.mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))(x))

    def test_reduce_lands_on_dst(self):
        x = jnp.arange(8.0)
        out = self._run(lambda v: dist.reduce(v, "data", dst=3), x)
        want = np.arange(8.0)
        want[3] = 28.0
        np.testing.assert_allclose(out, want)

    def test_scatter_from_src(self):
        import deepspeedsyclsupport_tpu as ds
        from jax.sharding import PartitionSpec as P

        topo = ds.build_topology(dp=8)
        # every rank holds an [8]-chunk; src's chunks get scattered
        x = jnp.arange(64.0).reshape(8, 8)
        out = np.asarray(jax.jit(shard_map(
            lambda v: dist.scatter(v[0], "data", src=2)[None, None],
            mesh=topo.mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))(x))
        # rank r returns element r of rank 2's row [16..24)
        np.testing.assert_allclose(out.reshape(-1), np.arange(16.0, 24.0))

    def test_p2p_moves_one_value(self):
        x = jnp.arange(8.0)
        out = self._run(lambda v: dist.p2p(v, src=1, dst=5, axis_name="data"),
                        x)
        want = np.arange(8.0)
        want[5] = 1.0
        np.testing.assert_allclose(out, want)

    def test_coalesced_and_aliases(self):
        x = jnp.arange(8.0)
        out = self._run(
            lambda v: dist.all_reduce_coalesced({"a": v, "b": 2 * v},
                                                "data")["b"], x)
        np.testing.assert_allclose(out, np.full(8, 56.0))
        out = self._run(lambda v: dist.inference_all_reduce(v, "data"), x)
        np.testing.assert_allclose(out, np.full(8, 28.0))

    def test_group_bookkeeping(self):
        g = dist.new_group([2, 5, 7])
        assert dist.get_all_ranks_from_group(g) == [2, 5, 7]
        assert dist.get_global_rank(g, 1) == 5
        assert g.size() == 3
        assert dist.get_world_group().size() == dist.get_device_count()
        with pytest.raises(TypeError):
            dist.get_global_rank("model", 1)  # mesh axes need coordinates


# =============================================== comms logger summary paths
class TestCommsLoggerSummary:
    """Tier-1 coverage for the straggler table and HLO-merge idempotency
    (ISSUE 4 satellite: these paths previously had no tests)."""

    def _fresh(self):
        from deepspeedsyclsupport_tpu.comm.comms_logging import CommsLogger

        lg = CommsLogger(enabled=True)
        lg.append("all_reduce", "data", 1024, (8,))
        lg.append("all_reduce", "data", 1024, (8,))
        lg.append("all_gather", "fsdp", 2048, (16,))
        return lg

    def test_log_summary_straggler_single_process(self):
        lg = self._fresh()
        lg.record_wall("train_batch", 1.5)
        lg.record_wall("ckpt", 0.25)
        table = lg.log_summary(show_straggler=True)
        assert "wall-clock (per host)" in table
        # single controller: self == min == max on every row
        for name, want in (("train_batch", "1.500"), ("ckpt", "0.250")):
            row = next(l for l in table.splitlines() if l.startswith(name))
            assert row.count(want) == 3, row

    def test_log_summary_without_straggler_omits_wall(self):
        lg = self._fresh()
        lg.record_wall("train_batch", 1.0)
        table = lg.log_summary(show_straggler=False)
        assert "wall-clock" not in table
        assert "all_reduce[data]" in table

    def test_record_hlo_idempotent(self):
        lg = self._fresh()
        hlo = {"all-reduce": {"count": 3, "total_bytes": 300},
               "all-gather": {"count": 1, "total_bytes": 100}}
        lg.record_hlo(hlo, tag="train_step")
        lg.record_hlo(hlo, tag="train_step")  # re-record: replace, not add
        snap = lg.snapshot()
        assert snap["xla::all-reduce[train_step]"] == {"count": 3,
                                                       "total_bytes": 300}
        assert snap["xla::all-gather[train_step]"] == {"count": 1,
                                                       "total_bytes": 100}
        # a different tag is a different program: separate keys
        lg.record_hlo(hlo, tag="eval_step")
        assert "xla::all-reduce[eval_step]" in lg.snapshot()
        # façade-recorded ops are untouched by the merge
        assert lg.snapshot()["all_reduce[data]"]["count"] == 2

    def test_summary_events_sanitized_and_declared(self):
        from deepspeedsyclsupport_tpu.monitor.telemetry import (
            EVENT_NAME_RE, is_declared)

        lg = self._fresh()
        lg.record_hlo({"all-reduce": {"count": 1, "total_bytes": 10}},
                      tag="train_step")
        events = lg.summary_events(step=7)
        assert events
        for name, value, step in events:
            assert step == 7
            assert name.startswith("Comm/")
            assert EVENT_NAME_RE.match(name), name
            assert is_declared(name), name
        named = dict((n, v) for n, v, _ in events)
        assert named["Comm/all_reduce.data/count"] == 2
        assert named["Comm/all_reduce.data/bytes"] == 2048
