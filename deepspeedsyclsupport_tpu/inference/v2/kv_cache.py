"""Blocked (paged) KV cache on device.

Analog of ``BlockedKVCache`` (``inference/v2/ragged/kv_cache.py``): a pool of
fixed-size KV blocks; sequences own arbitrary block lists, indirected through
block tables. Layout [L, num_blocks * block_size, KVH, D] — flat slot axis so
(de)referencing a slot is ``block_id * block_size + offset``. The serving
forwards carry the whole pool through their layer loop, scatter new rows
into ``[layer, slot]`` in place and hand the kernels the pool and the layer
(``model._pool_write`` / ``_scan_layers``): sliced by layer outside a
kernel, each layer cost a slice, a copy and a write-back of itself on the
v5e and the pool was held twice (PERF.md, PR 25).
"""
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import RaggedInferenceConfig


class MoeCounters(NamedTuple):
    """What the serving forwards of a sparse-expert model count about
    routing, on the device. ``load`` [L, E] int32: the (token, choice) rows
    each layer's router gave each expert, summed over every forward since
    the engine was built (live rows only: ``load[l].sum() == k × live
    tokens``). ``touched`` scalar int32: of the LAST forward, the experts
    with at least one live row, summed over layers — the expert weights that
    forward had to read."""
    load: jnp.ndarray
    touched: jnp.ndarray


class BlockedKV(NamedTuple):
    k: jnp.ndarray  # [L, num_blocks*block_size, KVH, D]
    v: jnp.ndarray
    # sparse-expert models only (None elsewhere: no leaf, the same program).
    # The counters ride with the pool because they live the pool's life: on
    # the device, through every forward's layer loop, donated and handed
    # back, so counting costs no launch and no transfer.
    moe: Optional[MoeCounters] = None

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]


def lane_padded_head_dim(head_dim: int, pad) -> int:
    """Mosaic constraint: the paged kernels DMA-slice the pool, and slice
    shapes must be lane-tile (128) aligned — head dims below/off 128 fail to
    compile on real TPU silicon ("Slice shape along dimension 2 must be
    aligned to tiling (128)"). The pool is therefore allocated with the head
    dim rounded up to the lane width on TPU; q/k/v are zero-padded at the
    attention seam (q pre-scaled by sqrt(d_pad/d) to compensate the impls'
    1/sqrt(trailing-dim) softmax scale) and the output sliced back, which
    leaves scores mathematically identical. ``pad`` None/0 = auto (128 on
    TPU, none elsewhere). HBM note: a d=64 model pays 2x KV pool for kernel decode."""
    if pad in (None, 0):
        pad = 128 if jax.default_backend() == "tpu" else 1
    return -(-head_dim // pad) * pad


def init_blocked_kv(model_config, cfg: RaggedInferenceConfig,
                    topology) -> BlockedKV:
    """Zeroed pool, allocated under ``jit`` straight into its placement on
    the engine's mesh: KV heads over ``model`` where they divide (the layout
    the TP-sharded wk/wv projections produce), replicated otherwise — never
    whole on the default device first."""
    d = lane_padded_head_dim(model_config.head_dim,
                             getattr(cfg, "head_dim_lane_pad", None))
    kvh = model_config.num_kv_heads
    shape = (model_config.num_layers, cfg.num_blocks * cfg.block_size, kvh, d)
    tp = topology.axis_sizes["model"]
    sharding = (topology.sharding(None, None, "model", None)
                if tp > 1 and kvh % tp == 0 else topology.replicated())
    zeros = jax.jit(lambda: jnp.zeros(shape, cfg.dtype),
                    out_shardings=sharding)
    moe = None
    if model_config.any_moe:
        moe = jax.jit(lambda: MoeCounters(
            jnp.zeros((model_config.num_layers, model_config.num_experts),
                      jnp.int32), jnp.zeros((), jnp.int32)),
            out_shardings=topology.replicated())()
    return BlockedKV(zeros(), zeros(), moe)


def kv_pool_stats(kv: BlockedKV, allocator) -> dict:
    """Occupancy + footprint of the paged pool, shape-only (no host sync):
    the ``Serve/kv_occupancy`` gauge's source and the operator's answer to
    "is the pool the bottleneck" — ``occupancy`` is the PHYSICAL fraction
    of blocks held by anyone (streams or the prefix index), while
    ``logical_occupancy`` prices every block-table entry at full cost
    (sum of refcounts / total): the gap between the two is exactly the HBM
    the prefix cache's cross-request sharing is saving. ``pool_bytes``
    counts BOTH k and v arrays at the (possibly lane-padded) allocated
    head dim."""
    total = allocator.num_blocks
    free = allocator.free_blocks
    physical = total - free
    # plain free-list allocators (no refcounts) degenerate to logical ==
    # physical, shared == 0 — the pre-sharing report
    logical = int(getattr(allocator, "logical_blocks", physical))
    shared = int(getattr(allocator, "shared_blocks", 0))
    per_slot = int(np.prod(kv.k.shape[2:])) * kv.k.dtype.itemsize \
        * kv.k.shape[0]
    return {"blocks_total": total, "blocks_free": free,
            "blocks_physical": physical, "blocks_logical": logical,
            "blocks_shared": shared,
            "occupancy": 1.0 - free / total,
            "logical_occupancy": logical / total,
            "pool_bytes": 2 * per_slot * kv.num_slots}


def build_block_copy_fn(block_size: int):
    """Jitted copy of one KV block (both k and v) to a fresh block — the
    copy-on-write seam for the prefix cache. ``src``/``dst`` are traced
    int32 operands, so ONE compiled program serves every block pair; the
    pool is donated (the copy is an in-place update as far as the caller
    is concerned)."""

    def _copy(kv: BlockedKV, src, dst) -> BlockedKV:
        L, _, H, D = kv.k.shape
        sizes = (L, block_size, H, D)
        ks = jax.lax.dynamic_slice(kv.k, (0, src * block_size, 0, 0), sizes)
        vs = jax.lax.dynamic_slice(kv.v, (0, src * block_size, 0, 0), sizes)
        return kv._replace(
            k=jax.lax.dynamic_update_slice(kv.k, ks,
                                           (0, dst * block_size, 0, 0)),
            v=jax.lax.dynamic_update_slice(kv.v, vs,
                                           (0, dst * block_size, 0, 0)))

    return jax.jit(_copy, donate_argnums=0)
