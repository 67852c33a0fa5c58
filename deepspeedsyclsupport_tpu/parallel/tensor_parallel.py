"""Tensor-parallel sharding helpers — the auto-TP analog.

The reference's inference auto-TP (``deepspeed/module_inject/auto_tp.py:483``
``AutoTP``) walks a torch module, pattern-detects Linears, and rewrites them into
``LinearLayer`` (column-split) / ``LinearAllreduce`` (row-split + allreduce).
On TPU the rewrite is unnecessary: TP is a *layout*, so auto-TP reduces to a rule
that maps parameter names/shapes → PartitionSpecs; XLA inserts the collectives
(the psum that ``LinearAllreduce`` hand-codes).

``auto_tp_rules`` is that rule for arbitrary user pytrees: column-parallel for
up-projections, row-parallel for down/output projections (recognized by the same
name conventions AutoTP keys on: ``o_proj/down_proj/out_proj/dense_4h_to_h/wo``…),
replicate everything else.
"""
from typing import Callable, Optional, Sequence, Tuple

# Output/down projections → row-parallel (shard input dim; XLA adds the psum).
# Mirrors AutoTP's allreduce-linear name list (auto_tp.py load policies).
ROW_PARALLEL_PATTERNS: Tuple[str, ...] = (
    "o_proj", "out_proj", "wo", "w_down", "down_proj", "dense_4h_to_h",
    "attention.dense", "fc2", "w2", "proj_out",
)
# Embedding-style tables → shard vocab dim
EMBEDDING_PATTERNS: Tuple[str, ...] = ("embed", "wte", "word_embeddings", "tok")


def _path_str(path) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "name", k))) for k in path).lower()


def auto_tp_rules(stacked_layer_key: Optional[str] = "layers",
                  row_patterns: Sequence[str] = ROW_PARALLEL_PATTERNS,
                  embed_patterns: Sequence[str] = EMBEDDING_PATTERNS
                  ) -> Callable:
    """Build an ``extra_rules(path, shape)`` callable for
    ``runtime/zero.tree_param_shardings`` from name heuristics."""

    def rules(path, shape):
        s = _path_str(path)
        ndim = len(shape)
        if ndim < 2:
            return None
        stacked = stacked_layer_key is not None and stacked_layer_key in s
        pre = (None,) if (stacked and ndim >= 3) else ()
        body = ndim - len(pre)
        if body < 2:
            return None
        if any(p in s for p in embed_patterns):
            return pre + ("model",) + (None,) * (body - 1)
        if any(p in s for p in row_patterns):
            # row-parallel: shard the (first body) input dim, fsdp the output dim
            return pre + ("model",) + ("fsdp",) + (None,) * (body - 2)
        # default column-parallel: output (last) dim over model, fsdp an input dim
        return pre + ("fsdp",) + (None,) * (body - 2) + ("model",)

    return rules


def column_parallel(*, stacked: bool = False) -> Tuple:
    """Spec for a [in, out] weight split on out (Megatron ColumnParallelLinear)."""
    return ((None,) if stacked else ()) + ("fsdp", "model")


def row_parallel(*, stacked: bool = False) -> Tuple:
    """Spec for a [in, out] weight split on in (Megatron RowParallelLinear)."""
    return ((None,) if stacked else ()) + ("model", "fsdp")


def vocab_parallel_embedding(table, input_ids):
    """Embedding lookup over a vocab-sharded table (Megatron
    VocabParallelEmbedding; reference analog: the sharded word-embedding
    containers in ``module_inject/``).

    A plain ``jnp.take`` on a table sharded ('model', 'fsdp') defeats the SPMD
    partitioner — it replicates the table then re-partitions ("involuntary full
    rematerialization"). This issues the Megatron pattern explicitly in a
    shard_map: each device looks up only ids inside its local vocab range,
    zero-fills the rest, and a psum over ``model`` combines; the hidden shards
    are all-gathered over ``fsdp``.

    table: [V, H] sharded ('model', 'fsdp'); input_ids: [B, S] sharded
    (('data','fsdp'), 'seq'). Returns [B, S, H] in the activation layout.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..comm import topology as topo_mod

    topo = topo_mod._WORLD_TOPOLOGY
    tp = topo.axis_sizes.get("model", 1) if topo is not None else 1
    # ANY manual axis (not just 'model') forbids the nested shard_map: the
    # ZeRO++ explicit step is manual over {data, fsdp} with 'model' auto, so
    # probing lax.axis_size('model') alone would miss it and this would
    # nest a shard_map over already-manual axes (trace error)
    in_manual_region = bool(jax.sharding.get_abstract_mesh().manual_axes)
    sizes = topo.axis_sizes if topo is not None else {}
    bdiv = sizes.get("data", 1) * sizes.get("fsdp", 1)
    divisible = (topo is not None
                 and input_ids.shape[0] % bdiv == 0
                 and input_ids.shape[1] % sizes.get("seq", 1) == 0
                 and table.shape[0] % tp == 0
                 and table.shape[1] % sizes.get("fsdp", 1) == 0)
    # fsdp > 1 alone (stage-3 tables with no TP: hidden sharded over fsdp,
    # e.g. the MiCS leg) also needs the explicit pattern — a plain take on
    # the fsdp-sharded table makes the cotangent reshard "involuntary full
    # rematerialization" in the partitioner
    if topo is None or (tp == 1 and sizes.get("fsdp", 1) == 1) \
            or in_manual_region or not divisible:
        return jnp.take(table, input_ids, axis=0)

    def body(tbl, ids):
        # tbl: [V/tp, H/fsdp]; ids: [B/(data·fsdp), S/sp]. The batch and the
        # hidden dim are BOTH fsdp-sharded, so assembling full-hidden rows
        # takes an all-to-all, not an all-gather: each rank looks up its
        # hidden slice for every row in its fsdp group, then the a2a sends
        # row-groups home while concatenating the hidden slices. (A plain
        # hidden all-gather would pair this rank's rows with OTHER ranks'
        # rows' hidden slices — corrupted embeddings.)
        vstart = lax.axis_index("model") * tbl.shape[0]
        ids_g = lax.all_gather(ids, "fsdp", axis=0, tiled=True)
        local = ids_g - vstart
        ok = jnp.logical_and(local >= 0, local < tbl.shape[0])
        x = jnp.take(tbl, jnp.where(ok, local, 0), axis=0)
        x = jnp.where(ok[..., None], x, jnp.zeros_like(x))
        x = lax.psum(x, "model")
        return lax.all_to_all(x, "fsdp", split_axis=0, concat_axis=2,
                              tiled=True)

    return jax.shard_map(
        body, mesh=topo.mesh,
        in_specs=(P("model", "fsdp"), P(("data", "fsdp"), "seq")),
        out_specs=P(("data", "fsdp"), "seq", None),
        check_vma=False)(table, input_ids)
