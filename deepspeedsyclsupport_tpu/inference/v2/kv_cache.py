"""Blocked (paged) KV cache on device.

Analog of ``BlockedKVCache`` (``inference/v2/ragged/kv_cache.py``): a pool of
fixed-size KV blocks; sequences own arbitrary block lists, indirected through
block tables. Layout [L, num_blocks * block_size, KVH, D] — flat slot axis so
(de)referencing a slot is ``block_id * block_size + offset``. ``L`` counts
the rows that cache, not the layers: a stack whose layers run several times
over shared weights (``ModelConfig.total_ut_steps``) has one row for every
(pass, layer) pair, pass ``u``'s layer ``l`` at ``u x num_layers + l``: a
pass attends to its own keys and values, so a block of 64 tokens holds all
``passes x layers`` rows of them and the allocator, the prefix cache and the
block copy see blocks as they do for any model. A model with a sparse-
attention indexer (``ModelConfig.index_topk``) has ONE MORE pool, ``idx``,
beside K and V (Keye: three arrays) or beside a latent pool's one array
(GLM-5: two): the indexer's one key a token and layer, as normed and rotated,
at the SAME flat slot as the token's other rows, so one block table addresses
them all and a block that is shared, copied, freed or requeued takes its
indexer keys with it. It is laid out TWO slots a row, [L, num_blocks *
block_size / 2, 2 x index_head_dim] (slot s in row s // 2, its key in the
lanes of its parity; a block's rows stay together): at 64 wide a row of its
own left the v5e compiler to store the pool slot-minor for the write and
copy all of it back for the read, every layer (48 ms a forward, PERF.md
section 6, PR 45), and padded to the 128 lanes it would be twice the bytes.
A latent-
attention model (``ModelConfig.kv_lora_rank``) caches ONE row a token and
layer, [L, num_blocks * block_size, D]: the normed latent and the rotated
key all heads share, and no V pool at all. The serving
forwards carry the whole pool through their layer loop, scatter new rows
into ``[layer, slot]`` in place and hand the kernels the pool and the layer
(``model._pool_write`` / ``_scan_layers``): sliced by layer outside a
kernel, each layer cost a slice, a copy and a write-back of itself on the
v5e and the pool was held twice (PERF.md, PR 25). A stack in which NO layer
caches a key (``ModelConfig.num_kv_layers`` 0: power retention) has pools
with no rows, ``[0, num_blocks * block_size, KVH, D]``: a sequence takes no
block, nothing is ever evicted for want of one, a token costs the pool 0
bytes, and what a sequence costs is its recurrent-state slot alone. A stack
whose layers are of TWO attention kinds (``ModelConfig.attn_period``: a
period of windowed layers and full ones) has two pools side by side, each
under a block table and an allocator of its own: K and V ``[L_f, num_blocks
* block_size, KVH, D]`` for the full layers, whose rows live as long as the
context, and ``wk`` and ``wv`` ``[L_w, window_blocks * block_size, KVH, D]``
for the windowed layers, whose blocks go back to their allocator once no
query of the sequence can see them (``ragged.SequenceDescriptor.
free_window_blocks``). A sequence then holds at most
:func:`window_blocks_a_sequence` blocks of the second whatever its context,
so that pool is sized by ``max_sequences`` and the bound and can never run
out; ``num_blocks`` sizes the first.
"""
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import RaggedInferenceConfig

# A Mamba layer's recurrent state in the serving pool: float32 beside bf16
# weights, as the family's serving instructions keep the SSM cache. Not a
# setting: a narrower state is rounded at every step of every sequence, a
# different result and not a faster one.
SSM_STATE_DTYPE = jnp.float32
# A power-retention layer's state and normaliser: float32 for the same
# reason (a sum over a whole context of products that cancel), a constant
# too.
RETENTION_STATE_DTYPE = jnp.float32
# A delta-rule layer's state: float32 for the same reason (every write first
# subtracts what the state holds for its key), a constant too.
KDA_STATE_DTYPE = jnp.float32
# A lightning layer's state: float32 for the same reason (its slow heads sum
# some hundreds of outer products, with no normaliser behind them), a
# constant too.
LIGHTNING_STATE_DTYPE = jnp.float32


class MoeCounters(NamedTuple):
    """What the serving forwards of a sparse-expert model count about
    routing, on the device. ``load`` [L_moe, E] int32 (the expert layers:
    leading dense layers have no row; E the router's WHOLE width, whatever
    part of the experts the program holds): the (token, choice) rows each
    layer's router gave each expert, summed over every forward since
    the engine was built (live rows only: ``load[l].sum() == k × live
    tokens``). ``touched`` scalar int32: of the LAST forward, the experts
    HELD HERE with at least one live row, summed over layers — the expert
    weights that forward had to read. ``rows`` scalar int32, only where the
    program holds a share of the experts (None, no leaf, where it holds
    them all: every live token then brings ``k`` rows a layer and the host
    can count them): of the last forward, the (token, choice) rows that
    went through the experts held here, summed over layers. ``tiles`` scalar
    int32 (None: a pool made by hand that does not count them): of the last
    forward, the row tiles its grouped GEMMs visit, summed over layers: every
    held expert's rows rounded up to whole tiles of
    ``ops.grouped_gemm.row_tile`` rows, the forward's static choice."""
    load: jnp.ndarray
    touched: jnp.ndarray
    rows: Optional[jnp.ndarray] = None
    tiles: Optional[jnp.ndarray] = None


class BlockedKV(NamedTuple):
    k: jnp.ndarray  # [L, num_blocks*block_size, KVH, D]; latent: [L, .., D]
    v: Optional[jnp.ndarray]  # None for a latent pool: V is K's leading lanes
    # sparse-expert models only (None elsewhere: no leaf, the same program).
    # The counters ride with the pool because they live the pool's life: on
    # the device, through every forward's layer loop, donated and handed
    # back, so counting costs no launch and no transfer.
    moe: Optional[MoeCounters] = None
    # a model with recurrent state only (None elsewhere: no leaf, the same
    # program): the state, per state layer (``ModelConfig.state_layers``)
    # and sequence SLOT, fixed in size whatever the context, of ONE of three
    # kinds (:attr:`state`). Mamba-2 layers (``ModelConfig.layer_pattern``'s
    # ``M``, or its ``H``, which have a row of ``k`` and ``v`` as well):
    # ``ssm`` [L_m, S + 1, g, n, (h / g) x p] in :data:`SSM_STATE_DTYPE` and
    # ``conv`` [L_m, kernel - 1, S + 1, channels], the convolution's tail,
    # in the pool's dtype (``ops/ssm.py`` has the layout's why).
    # Power-retention layers (``ModelConfig.retention_degree``): ``ret_s``
    # [L, S + 1, KVH, D, features] and ``ret_z`` [L, S + 1, KVH, features]
    # in :data:`RETENTION_STATE_DTYPE` (``ops/retention.py``). Delta-rule
    # layers (``layer_pattern``'s ``K``): ``kda_s`` [L_k, S + 1, heads, dim,
    # dim] in :data:`KDA_STATE_DTYPE` and ``kda_conv`` [L_k, kernel - 1, S +
    # 1, 3 x heads x dim], the convolution's tail over q | k | v, in the
    # pool's dtype (``ops/kda.py``). Slot ``S``
    # is the sink padding rows write to. They ride the forwards as the pool
    # does: donated, in the layer loop's carry, updated in place. Nothing
    # resets a slot: a piece whose first position is 0 starts from zeros.
    ssm: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    ret_s: Optional[jnp.ndarray] = None
    ret_z: Optional[jnp.ndarray] = None
    kda_s: Optional[jnp.ndarray] = None
    kda_conv: Optional[jnp.ndarray] = None
    # Lightning layers (``layer_pattern``'s ``L``): ``la_s`` [L_l, S + 1,
    # heads, dim, dim] in :data:`LIGHTNING_STATE_DTYPE`, the key's channels
    # on the sublanes (``ops/ssm.py``); a kind of ONE array: no convolution,
    # no tail.
    la_s: Optional[jnp.ndarray] = None
    # a looped stack only (``ModelConfig.total_ut_steps`` > 1; None
    # elsewhere: no leaf, the same program): [passes] int32, the rows the
    # forwards unembedded for a live sequence, by the pass the exit rule
    # took their logits from, summed since the engine was built (at the
    # published threshold 1.0 all in the last). Rides with the pool as
    # ``moe`` does.
    exit_pass: Optional[jnp.ndarray] = None
    # a model with a sparse-attention indexer only (``ModelConfig.
    # index_topk``; None elsewhere: no leaf, the same program): the
    # indexer's keys in the pool's dtype, one a token and layer at the slot
    # of its K and V, two slots a row: [L, num_blocks*block_size / 2,
    # 2 x index_head_dim]
    idx: Optional[jnp.ndarray] = None
    # a stack of two attention kinds only (``ModelConfig.attn_period``; None
    # elsewhere: no leaf, the same program): the WINDOWED layers' keys and
    # values, [L_w, window_blocks * block_size, KVH, D], addressed by the
    # sequences' window tables
    wk: Optional[jnp.ndarray] = None
    wv: Optional[jnp.ndarray] = None
    # a model whose attention reads blocks chosen from pooled keys only
    # (``ModelConfig.sparse_block_topk``; None elsewhere: no leaf, the same
    # program): ``ck`` [L, num_blocks, block / stride, KVH, D] in the pool's
    # dtype, the mean of every window of keys at the PAGE the window starts
    # in, so the block table addresses it and a page that is freed or
    # requeued takes its pooled keys with it; and ``bsa`` int32, what
    # the last forward's sparse layers counted (``bsa.COUNTS``), which rides
    # as ``moe`` does
    ck: Optional[jnp.ndarray] = None
    bsa: Optional[jnp.ndarray] = None

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def window_slots(self) -> int:
        """Slots of the windowed layers' pool (0: the model has one pool)."""
        return 0 if self.wk is None else self.wk.shape[1]

    @property
    def state_names(self):
        """The fields that hold recurrent state: one kind's two, or ()."""
        return next((pair for pair in STATE_NAMES
                     if getattr(self, pair[0]) is not None), ())

    @property
    def state_kind(self) -> Optional[str]:
        """``ssm`` | ``ret`` | ``kda`` | ``la`` (None: no recurrent state): what the
        ``round`` record's counts of the state layers are named by."""
        names = self.state_names
        return names[0].split("_")[0] if names else None

    @property
    def state(self):
        """The recurrent-state arrays there are: (ssm, conv), (ret_s,
        ret_z), (kda_s, kda_conv), (la_s,) or ()."""
        return tuple(getattr(self, n) for n in self.state_names)

    @property
    def state_slots(self) -> int:
        """Sequence slots of the recurrent state, the sink not counted (0:
        the model keeps none)."""
        return self.state[0].shape[1] - 1 if self.state else 0

    def with_state(self, state) -> "BlockedKV":
        """This cache with ``state`` (as :attr:`state` gives it) in the
        place of its own."""
        return self._replace(**dict(zip(self.state_names, state)))

    @property
    def pools(self):
        """The pool arrays there are, under :data:`POOL_NAMES`: (k, v), (k,)
        for a latent pool, (k, v, idx) or a latent pool's (k, idx) beside
        a sparse-attention indexer,
        (k, v, ck) beside pooled keys, (k, v, wk, wv) for a stack of two
        attention kinds."""
        return tuple(pool for pool in map(self.__getattribute__, POOL_NAMES)
                     if pool is not None)

    def with_pools(self, pools) -> "BlockedKV":
        """This cache with ``pools`` (as :attr:`pools` gives them) in the
        place of its own."""
        return self._replace(**dict(zip(
            (n for n in POOL_NAMES if getattr(self, n) is not None), pools)))


# the fields of :class:`BlockedKV` that are pools addressed by block tables
POOL_NAMES = ("k", "v", "idx", "ck", "wk", "wv")
# ... and those that are recurrent state addressed by sequence slot, the
# arrays of a kind of state layer together; the FIRST has its slots on axis 1
STATE_NAMES = (("ssm", "conv"), ("ret_s", "ret_z"), ("kda_s", "kda_conv"),
               ("la_s",))


def lane_padded_head_dim(head_dim: int, pad) -> int:
    """Mosaic constraint: the paged kernels DMA-slice the pool, and slice
    shapes must be lane-tile (128) aligned — head dims below/off 128 fail to
    compile on real TPU silicon ("Slice shape along dimension 2 must be
    aligned to tiling (128)"). The pool is therefore allocated with the head
    dim rounded up to the lane width on TPU; q/k/v are zero-padded at the
    attention seam (q pre-scaled by sqrt(d_pad/d) to compensate the impls'
    1/sqrt(trailing-dim) softmax scale) and the output sliced back, which
    leaves scores mathematically identical. ``pad`` None = auto (128 on
    TPU, none elsewhere); otherwise a positive int, which the config
    checks. HBM note: a d=64 model pays 2x KV pool for kernel decode."""
    if pad is None:
        pad = 128 if jax.default_backend() == "tpu" else 1
    return -(-head_dim // pad) * pad


def window_blocks_a_sequence(window: int, cfg: RaggedInferenceConfig) -> int:
    """The most blocks of the windowed layers' pool ONE sequence holds: what
    a query at the sequence's next position can still see, ``window`` keys,
    the longest chunk a forward appends before anything is given back,
    ``max_tokens_per_batch``, and the two blocks those straddle at their
    ends (``window + max_tokens_per_batch + block_size`` tokens where both
    are whole blocks); never more than a context has."""
    bs = cfg.block_size
    return min((window + cfg.max_tokens_per_batch + 2 * bs - 3) // bs,
               cfg.blocks_per_seq)


def init_blocked_kv(model_config, cfg: RaggedInferenceConfig,
                    topology) -> BlockedKV:
    """Zeroed pool, allocated under ``jit`` straight into its placement on
    the engine's mesh: KV heads over ``model`` where they divide (the layout
    the TP-sharded wk/wv projections produce), replicated otherwise — never
    whole on the default device first."""
    pad = getattr(cfg, "head_dim_lane_pad", None)
    latent = model_config.latent_kv_dim
    kvh = model_config.num_kv_heads
    row = (lane_padded_head_dim(latent, pad),) if latent else (
        kvh, lane_padded_head_dim(model_config.head_dim, pad))
    shape = (model_config.num_kv_layers, cfg.num_blocks * cfg.block_size,
             *row)
    tp = topology.axis_sizes["model"]
    sharding = (topology.sharding(None, None, "model", None)
                if tp > 1 and kvh % tp == 0 and not latent
                else topology.replicated())
    zeros = jax.jit(lambda: jnp.zeros(shape, cfg.dtype),
                    out_shardings=sharding)
    moe = None
    if model_config.any_moe:
        share = model_config.experts_held != model_config.num_experts
        moe = jax.jit(lambda: MoeCounters(
            jnp.zeros((model_config.num_moe_layers, model_config.num_experts),
                      jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32) if share else None,
            jnp.zeros((), jnp.int32)),
            out_shardings=topology.replicated())()
    state = {}
    if model_config.mamba_layers:
        mc, lead = model_config, (model_config.mamba_layers,
                                  cfg.max_sequences + 1)
        g = mc.ssm_n_groups
        state = jax.jit(lambda: dict(
            ssm=jnp.zeros((*lead, g, mc.ssm_state_size, mc.ssm_d_inner // g),
                          SSM_STATE_DTYPE),
            conv=jnp.zeros((lead[0], mc.ssm_conv_kernel - 1, lead[1],
                            mc.ssm_conv_dim), cfg.dtype)),
            out_shardings=topology.replicated())()
    if model_config.retention_degree:
        from ...ops.retention import state_dim

        mc = model_config
        lead = (mc.num_layers, cfg.max_sequences + 1, kvh)
        dim = state_dim(mc.head_dim)
        if np.prod(lead) * mc.head_dim * dim >= 2**31:
            raise ValueError(
                f"the retention state [{lead}, {mc.head_dim}, {dim}] passes "
                f"2^31 elements, which one array may not: fewer "
                f"max_sequences or layers")
        state = jax.jit(lambda: dict(
            ret_s=jnp.zeros((*lead, mc.head_dim, dim), RETENTION_STATE_DTYPE),
            ret_z=jnp.zeros((*lead, dim), RETENTION_STATE_DTYPE)),
            out_shardings=topology.replicated())()
    if model_config.pattern_count("K"):
        mc, lead = model_config, (model_config.pattern_count("K"),
                                  cfg.max_sequences + 1)
        h, d = mc.kda_num_heads, mc.kda_head_dim
        if np.prod(lead) * h * d * d >= 2**31:
            raise ValueError(
                f"the delta-rule state [{lead}, {h}, {d}, {d}] passes 2^31 "
                f"elements, which one array may not: fewer max_sequences "
                f"or layers")
        state = jax.jit(lambda: dict(
            kda_s=jnp.zeros((*lead, h, d, d), KDA_STATE_DTYPE),
            kda_conv=jnp.zeros((lead[0], mc.kda_conv_kernel - 1, lead[1],
                                3 * mc.kda_dim), cfg.dtype)),
            out_shardings=topology.replicated())()
    if model_config.pattern_count("L"):
        mc, lead = model_config, (model_config.pattern_count("L"),
                                  cfg.max_sequences + 1)
        h, d = mc.lightning_heads, mc.lightning_head_dim
        if np.prod(lead) * h * d * d >= 2**31:
            raise ValueError(
                f"the lightning state [{lead}, {h}, {d}, {d}] passes 2^31 "
                f"elements, which one array may not: fewer max_sequences "
                f"or layers")
        state = jax.jit(lambda: dict(
            la_s=jnp.zeros((*lead, h, d, d), LIGHTNING_STATE_DTYPE)),
            out_shardings=topology.replicated())()
    if model_config.sparse_block_topk:
        mc = model_config
        if cfg.block_size != mc.sparse_block_size:
            raise ValueError(
                f"block_size {cfg.block_size}: a block the sparse attention "
                f"selects is a page of the pool, so block_size must equal "
                f"sparse_block_size {mc.sparse_block_size}")
        from .bsa import COUNTS

        shape_c = (shape[0], cfg.num_blocks,
                   mc.sparse_block_size // mc.sparse_block_stride, *row)
        state.update(jax.jit(lambda: dict(
            ck=jnp.zeros(shape_c, cfg.dtype),
            bsa=jnp.zeros((len(COUNTS),), jnp.int32)),
            out_shardings=topology.replicated())())
    if model_config.index_topk:
        if cfg.block_size % 2:
            raise ValueError("a sparse-attention indexer's keys lie two "
                             "slots a row: block_size must be even")
        shape_i = (shape[0], shape[1] // 2, 2 * model_config.index_head_dim)
        state["idx"] = jax.jit(lambda: jnp.zeros(shape_i, cfg.dtype),
                               out_shardings=topology.replicated())()
    if model_config.window_layers:
        shape_w = (model_config.window_layers, cfg.max_sequences
                   * window_blocks_a_sequence(model_config.period_window, cfg)
                   * cfg.block_size, *row)
        zeros_w = jax.jit(lambda: jnp.zeros(shape_w, cfg.dtype),
                          out_shardings=sharding)
        state.update(wk=zeros_w(), wv=zeros_w())
    if model_config.total_ut_steps > 1:
        state["exit_pass"] = jax.jit(
            lambda: jnp.zeros((model_config.total_ut_steps,), jnp.int32),
            out_shardings=topology.replicated())()
    return BlockedKV(zeros(), None if latent else zeros(), moe, **state)


def state_pool_stats(kv: BlockedKV, live: int) -> Optional[dict]:
    """What the recurrent state of a model costs, of any kind (Mamba-2
    layers' SSM state and convolution tail, power-retention layers' state
    and normaliser, delta-rule layers' state and convolution tail, lightning
    layers' state; None for a model without): bytes a sequence slot over
    all its state layers, the slots there are (the sink not counted) and
    how many are a sequence's now, the state's dtype and its layers.
    Shape-only, no transfer."""
    if not kv.state:
        return None
    slots = kv.state_slots
    per_slot = sum(a.size // (slots + 1) * a.dtype.itemsize
                   for a in kv.state)
    return {"bytes_per_slot": per_slot, "slots": slots, "slots_live": live,
            "dtype": str(kv.state[0].dtype), "layers": kv.state[0].shape[0],
            "pool_bytes": per_slot * (slots + 1)}


def kv_pool_stats(kv: BlockedKV, allocator) -> dict:
    """Occupancy + footprint of the paged pool, shape-only (no host sync):
    the ``Serve/kv_occupancy`` gauge's source and the operator's answer to
    "is the pool the bottleneck" — ``occupancy`` is the PHYSICAL fraction
    of blocks held by anyone (streams or the prefix index), while
    ``logical_occupancy`` prices every block-table entry at full cost
    (sum of refcounts / total): the gap between the two is exactly the HBM
    the prefix cache's cross-request sharing is saving. ``pool_bytes``
    counts every pool array there is (k and v; a latent pool has one) at
    the (possibly lane-padded) allocated row width."""
    total = allocator.num_blocks
    free = allocator.free_blocks
    physical = total - free
    # plain free-list allocators (no refcounts) degenerate to logical ==
    # physical, shared == 0 — the pre-sharing report
    logical = int(getattr(allocator, "logical_blocks", physical))
    shared = int(getattr(allocator, "shared_blocks", 0))
    kinds = {}
    if hasattr(allocator, "window"):
        # a stack of two attention kinds: ``allocator`` answers over both
        # pools; each kind's own blocks beside it
        kinds = {f"{kind}_blocks_{what}": n
                 for kind, a in (("full", allocator.full),
                                 ("window", allocator.window))
                 for what, n in (("total", a.num_blocks),
                                 ("held", a.num_blocks - a.free_blocks))}
    return {**kinds, "blocks_total": total, "blocks_free": free,
            "blocks_physical": physical, "blocks_logical": logical,
            "blocks_shared": shared,
            "occupancy": 1.0 - free / total,
            "logical_occupancy": logical / total,
            "pool_bytes": sum(pool.size * pool.dtype.itemsize
                              for pool in kv.pools)}


def build_block_copy_fn(block_size: int):
    """Jitted copy of one KV block (k and, where the pool has one, v; its
    indexer keys where there are any) to a fresh block — the copy-on-write
    seam for the prefix cache. ``src``/``dst`` are traced int32 operands, so
    ONE compiled program serves every block pair; the pool is donated (the
    copy is an in-place update as far as the caller is concerned)."""

    def _copy_pool(pool, src, dst):
        rest = (0,) * (pool.ndim - 2)
        block = jax.lax.dynamic_slice(
            pool, (0, src * block_size, *rest),
            (pool.shape[0], block_size, *pool.shape[2:]))
        return jax.lax.dynamic_update_slice(
            pool, block, (0, dst * block_size, *rest))

    def _copy(kv: BlockedKV, src, dst) -> BlockedKV:
        return kv.with_pools([_copy_pool(pool, src, dst)
                              for pool in kv.pools])

    return jax.jit(_copy, donate_argnums=0)
