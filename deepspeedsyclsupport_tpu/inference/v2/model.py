"""Ragged forward over the paged KV cache.

The compute core of the v2 engine — the role of the reference's CUDA ragged
kernel set (``inference/v2/kernels/ragged_ops/``):

* ``linear_blocked_kv_rotary`` — fused QKV + RoPE + paged-KV append → here the
  qkv einsums + :func:`apply_rope` + one scatter into the flat slot axis.
* ``blocked_flash`` (attention over ragged atoms) → the ``kernel`` impl
  (:func:`_prefill_kernel_impl`: the Pallas ragged kernel over the atoms
  and, for the one-token chunks, the decode entry's one-row tile) on the
  chip; :func:`_paged_attention`, an exact XLA implementation gathering
  each slot's block-table-resolved KV, is the correctness reference the
  kernel is tested against (the kernel-vs-reference pattern the CUDA tests
  use, SURVEY.md §4).
* ``logits_gather`` — only each sequence's last scheduled token reaches the
  unembedding matmul (``engine_v2.py`` forward tail).

Operates on ONE flat token stream [T] with per-token (seq-slot, position)
routing — batch composition never changes the compiled program.

Reuses the training model's parameters and sublayer math (``models/layers.py``)
— the weight-sharing the reference needs separate inference containers for.
The full architecture-config surface (layernorm/rmsnorm, rope/learned/alibi
positions, partial rotary, gated/standard MLP, parallel residual blocks,
biases, sliding window) serves here exactly as in training — the analog of the
reference's v2 model zoo (``inference/v2/model_implementations/{llama_v2,
mistral,mixtral,opt,falcon,phi}.py``) as config axes instead of classes.

Three things serve ONLY here (``models/transformer.py`` refuses to train
them): latent attention (``cfg.kv_lora_rank``: :func:`_mla_rows` caches one
``[c_kv | k_r]`` row a token and attends in absorbed form through the same
kernels), hyper-connection streams (``cfg.hc_mult``: :func:`_hc_block`
carries ``[n, T, d]`` and mixes it around each sublayer) and leading dense
layers before the expert layers (``params["dense_layers"]``, a stack of its
own, walked first by :func:`_scan_layers`). And a fourth: a hybrid stack
(``cfg.layer_pattern``: Mamba-2, expert and attention layers, ONE mixer a
layer), which :func:`_walk_pattern` walks over three stacks of parameters,
the KV pool (a row for the attention layers only) and the recurrent state
of the Mamba layers (``ops/ssm.py``), each with an index of its own. And a
fifth: a looped stack (``cfg.total_ut_steps``: the layers run several times
over shared weights, :func:`_scan_passes`), whose pool has a row for every
(pass, layer) pair and whose logits the exit gate chooses among the passes.
And a sixth: power retention (``cfg.retention_degree``) in the place of
attention on the uniform block: :func:`_retention_rows` gives the rows,
``ops/retention.py`` the recurrence against a state that rides the layer
loop's carry behind the (empty) pools (:func:`_scan_layers`). And a seventh:
a stack of two attention kinds (``cfg.attn_period``: windowed layers with
rotary positions and full layers with none, in a fixed period), which
:func:`_scan_layers` scans a PERIOD at a time, the period's layers unrolled
with static kind (:class:`AttnKind`), each kind with a pool, a block table
and a row index of its own. And an eighth: the gated delta rule
(``layer_pattern``'s ``K`` layers, ``ops/kda.py``: :func:`_kda_mixer`), a
third kind of recurrent state behind the same slots, which
:func:`_walk_pattern` walks as it walks Mamba-2's, beside attention layers
whose output is gated (``cfg.attn_out_gate``). And a ninth: lightning linear
attention (``layer_pattern``'s ``L`` layers: :func:`_lightning_mixer`,
``ops/ssm.py``'s convolution-free entries), a fourth kind of state behind the
slots, beside dense feed-forward parts (``F``) and attention layers that read
a SELECTION of blocks chosen from pooled keys (``cfg.sparse_block_topk``:
``bsa.py``), every sublayer under muP's ``cfg.residual_scale``. And a tenth:
attention heads and a Mamba-2 mixer SIDE BY SIDE in one layer
(``layer_pattern``'s ``H`` layers, falcon_h1): one norm, both mixers on the
same normed rows, a KV row and a state slot at the same index, one sum into
the stream, under muP's twelve multipliers inside the layer (``cfg.mup``).
And an eleventh: a learned indexer's selection of cached tokens
(``cfg.index_topk``: ``dsa.py``) over K and V a KV head or INSIDE latent
attention, over the latent pool's one row a token, its queries then a
projection of the query latent (``cfg.index_q_latent``:
:func:`_mla_query_latent` makes it once for both).

Both forwards trace under the training model's MFU regions
(``monitor/mfu.region_scope``; labels in the compiled text's metadata, no
other trace of them): ``embed`` (:func:`_embed` with the choice of the token
ids), ``attn`` (the token mixer of whatever kind with the norm that feeds it,
its residual add, a hyper-connection sublayer's maps around it, and the pool
addresses the forward computes once for all layers), ``mlp`` (the channel
mixer, dense or sparse, with its norm, its residual add and the experts'
counters) and ``head`` (:func:`_final_norm`, the gather of the rows that are
unembedded, :func:`_unembed`, a looped stack's exit). They are opened at the
few shared functions (:func:`_block`, :func:`_hc_block`,
:func:`_walk_pattern`'s ``one``, :func:`_scan_passes`, the two forwards'
own first and last lines); every named sub-scope nests inside one. What is
left under NO region is what no line here asked for: the layer loops'
counters and slices of the stacked weights, and whatever copy, pad or
transpose the compiler placed outside every scope
(``docs/observability.md``).
"""
import functools
from contextlib import nullcontext
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kv_cache import BlockedKV, MoeCounters
from .module_registry import register_impl, select_impl
from ...models.layers import (alibi_slopes, apply_rope, mlp_block, norm,
                              qk_norm, rms_norm, scaled)
from ...monitor.mfu import region_scope, scope
from ...ops.grouped_gemm import row_tile, tile_visits

NEG_INF = jnp.finfo(jnp.float32).min


class AttnKind(NamedTuple):
    """What is static about the attention of layer ``j`` of a period of
    ``cfg.attn_kinds``: its ``window`` (None: full) and ``pos_embed``; and,
    of a stack of two kinds (``cfg.attn_period``; ``label`` None for every
    other model, whose one kind has every pool and row ``l``), which of the
    carried pools are its own (``pools``: (0, 2) K and V, (2, 4) the
    windowed layers' ``wk`` and ``wv``; None: all), what a profile calls it
    (``swa`` | ``full``), and where its rows lie in its pool: a period has ``per``
    layers of the kind and this is the ``rank``-th of them."""
    window: Optional[int]
    pos_embed: str
    label: Optional[str] = None
    pools: Optional[Tuple[int, int]] = None
    period: int = 1
    per: int = 1
    rank: int = 0

    @classmethod
    def of(cls, cfg, j: int) -> "AttnKind":
        window, pos = cfg.attn_kinds[j]
        if cfg.attn_period is None:
            return cls(window, pos)
        mine = [i for i, (w, _) in enumerate(cfg.attn_period)
                if (w is None) == (window is None)]
        return cls(window, pos, "full" if window is None else "swa",
                   (0, 2) if window is None else (2, 4),
                   len(cfg.attn_period), len(mine), mine.index(j))

    @property
    def windowed(self) -> bool:
        """Whether the kind's rows lie in the windowed layers' pool."""
        return self.pools is not None and self.pools[0] > 0

    def split(self, pools):
        """``(before, the kind's own, after)`` of the carried ``pools``."""
        lo, hi = self.pools or (0, len(pools))
        return pools[:lo], pools[lo:hi], pools[hi:]

    def row(self, l):
        """The row of its pool that layer ``l`` (traced) reads and writes."""
        if self.label is None:
            return l
        return l // self.period * self.per + self.rank

    def scope(self):
        return scope(f"attn_{self.label}") if self.label else nullcontext()


class PrefillAttnContext(NamedTuple):
    """Everything a prefill-attention implementation may consume — the
    uniform contract registered impls are called with (the reference's
    ConfigBundle role, ``modules/module_registry.py``). ``k_cache`` /
    ``v_cache`` are the WHOLE pool [L, num_slots, KVH, D] and ``layer`` the
    (traced) layer to read: the kernels index it in their DMAs, the other
    impls slice ``pool[layer]`` at their seam (:func:`_layer_kv`). A latent
    pool [L, num_slots, D] has ``v_cache`` None and ``v_dim``, the leading
    lanes of its rows that are the value."""
    k_cache: Any
    v_cache: Any
    layer: Any
    token_seq: Any
    token_pos: Any
    block_tables: Any
    block_size: int
    alibi: Any
    window: Optional[int]
    atom_qidx: Any = None
    atom_pos0: Any = None
    atom_qlen: Any = None
    atom_tables: Any = None
    atom_inv: Any = None
    dec_row: Any = None
    dec_len: Any = None
    v_dim: Optional[int] = None
    # what a profile calls the kernels of a stack of two attention kinds
    # (``paged_<kind>_prefill`` / ``paged_<kind>_decode``; None: the names
    # every other model's have)
    kind: Optional[str] = None


def _kernel_names(kind: Optional[str]):
    """``name=`` of the atoms' call and of the one-row call under ``kind``
    (:attr:`AttnKind.label`), or nothing: the kernels' own names."""
    if kind is None:
        return {}, {}
    return ({"name": f"paged_{kind}_prefill"},
            {"name": f"paged_{kind}_decode"})


# ------------------------------------------------------ the serving layout
# The model's public tree stores a projection ``[in, out]``
# (``models/transformer.py``). The v5e compiler wants a product's weight with
# the CONTRACTED axis minor, and a program's arguments arrive in the default
# layout, so handed the public tree it re-lays them inside the program, every
# time it runs (Ouro's q, k and v: three stacks, 1.13 GiB and 3 ms a forward,
# PERF.md section 6, PR 64; DeepSeek-V2's ``w_qb`` and ``w_kvb``: 176 MiB a
# layer, PR 66). The two serving forwards therefore take the leaves below as
# their products read them, always: a square matrix cannot say which way it
# lies. Whoever hands a forward a tree lays it out first (the engine once,
# at load).
QKV = ("wq", "wk", "wv")        # :func:`_qkv`'s and :func:`_lightning_mixer`'s
# ``[..., out, in]``: those, latent attention's queries (:func:`_mla_rows`)
# and a sparse-attention indexer's (``dsa.index_rows``)
TURNED = (*QKV, "w_qb", "w_qi")
# latent attention's ``w_kvb`` [..., r, h x (nope + v)] is read twice, a
# head at a time: as ``W_UK`` under the queries (:func:`_mla_rows`) and as
# ``W_UV`` over the attended latents (:func:`_mla_out`). A reshape and a lane
# slice inside the forward cost a copy each, so it is held as the two parts,
# head-major: ``w_uk`` [..., h, r, nope] and ``w_uv`` [..., h, v, r].
SPLIT, PARTS = "w_kvb", ("w_uk", "w_uv")


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


@functools.lru_cache(maxsize=None)
def _split(heads: int, nope: int):
    """``w_kvb -> (w_uk, w_uv)`` (one function a pair of widths: a ``jit``
    of it is traced once)."""
    def split(w):
        w = w.reshape(*w.shape[:-1], heads, -1)          # [..., r, h, n + v]
        return (jnp.moveaxis(w[..., :nope], -3, -2),     # [..., h, r, n]
                jnp.moveaxis(w[..., nope:], -3, -1))     # [..., h, v, r]
    return split


def _join(w_uk, w_uv):
    """:func:`_split`'s inverse, bit for bit."""
    w = jnp.concatenate([jnp.moveaxis(w_uk, -2, -3),
                         jnp.moveaxis(w_uv, -1, -3)], axis=-1)
    return w.reshape(*w.shape[:-2], -1)


def _swap_spec(s):
    return [[*s[:-2], s[-1], s[-2]]]


def _split_spec(s):     # w_kvb's (..., r's axis, out's): the heads take out's
    return [[*s[:-2], s[-1], s[-2], None], [*s[:-2], s[-1], None, s[-2]]]


def _join_spec(s):      # w_uk's (..., the heads' axis, r's, None)
    return [[*s[:-3], s[-2], s[-3]]]


def _moved(fn, specs, *xs):
    """``fn(*xs)`` where ``xs`` live, the shardings following the axes
    (``specs``: the first argument's partition spec, padded to its rank, ->
    each result's): arrays on a mesh (under ``jit``, one leaf alive at a
    time; the arguments are never donated: they are the caller's), traced
    values, or ``ShapeDtypeStruct``s."""
    x = xs[0]
    if isinstance(x, jax.core.Tracer) or not hasattr(x, "sharding"):
        return fn(*xs)
    outs, tree = jax.tree_util.tree_flatten(jax.eval_shape(fn, *xs))
    shardings = [x.sharding] * len(outs)
    if isinstance(x.sharding, jax.sharding.NamedSharding):
        spec = [*x.sharding.spec] + [None] * (x.ndim - len(x.sharding.spec))
        shardings = [jax.sharding.NamedSharding(
            x.sharding.mesh, jax.sharding.PartitionSpec(*s))
            for s in specs(spec)]
    if isinstance(x, jax.ShapeDtypeStruct):
        return tree.unflatten([
            jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=at)
            for o, at in zip(outs, shardings)])
    return jax.jit(fn, out_shardings=tree.unflatten(shardings))(*xs)


def _lay(node, cfg, plain):
    """The dict ``node`` (the tree, or a layer of it) with each leaf the
    forwards read otherwise laid out as they read it, BY ITS NAME.
    ``plain(x)``: the leaf as a plain array (or shape), None to leave it as
    it is."""
    out = {}
    for name, x in node.items():
        if isinstance(x, dict):
            out[name] = _lay(x, cfg, plain)
            continue
        w = plain(x)
        if w is None:
            out[name] = x
        elif name == SPLIT:
            out.update(zip(PARTS, _moved(
                _split(cfg.num_heads, cfg.qk_nope_head_dim), _split_spec, w)))
        elif name in TURNED:
            out[name] = _moved(_swap, _swap_spec, w)
        else:
            out[name] = w
    return out


def serving_layout(params, cfg):
    """The model's public tree as the two serving forwards of a model of
    configuration ``cfg`` take it: every leaf of :data:`TURNED` ``[..., out,
    in]``, :data:`SPLIT` as its :data:`PARTS`, everything else as it is (the
    very object). On arrays, traced values and ``ShapeDtypeStruct``s alike;
    with :func:`public_layout` the only place that knows which leaves
    differ. A ``QuantTensor`` keeps its form, the public one:
    :func:`_dequant` lays out what it materialises."""
    from ...compression.quantize import QuantTensor

    return _lay(params, cfg,
                lambda x: None if isinstance(x, QuantTensor) else x)


def public_layout(params):
    """:func:`serving_layout`'s inverse, bit for bit: the turned leaves
    turned back, the parts joined."""
    from ...compression.quantize import QuantTensor

    out = {}
    for name, x in params.items():
        if isinstance(x, dict):
            out[name] = public_layout(x)
        elif name == PARTS[0]:
            out[SPLIT] = _moved(_join, _join_spec, x, params[PARTS[1]])
        elif name in TURNED and not isinstance(x, QuantTensor):
            out[name] = _moved(_swap, _swap_spec, x)
        elif name != PARTS[1]:
            out[name] = x
    return out


def _dequant(p, dtype, cfg):
    """ZeRO-Inference: materialize int8 QuantTensor leaves per layer, each
    in the serving layout (the ``QuantTensor`` holds the public one)."""
    from ...compression.quantize import QuantTensor

    return _lay(p, cfg, lambda x: x.dequantize(dtype)
                if isinstance(x, QuantTensor) else None)


def _mlp(p, y, cfg, live, experts=None):
    """Per-layer MLP over flat tokens [T, D]: dense (GLU or fc1/fc2), or exact
    top-k MoE via grouped GEMMs (the moe_scatter/cutlass-multi-GEMM/moe_gather
    analog, ``parallel/moe.moe_mlp_nodrop``) over the ``live`` rows [T].
    ``experts``: what :func:`_scan_layers` kept out of ``p``, ``(the stacked
    expert leaves [L_moe, E, ., .], this layer's index in them)``, read where
    they lie; a leaf still in ``p["moe"]`` is the layer's own slice.
    Returns (out, the rows the router gave each expert [E] — None when
    dense)."""
    if "moe" in p:    # by the tree: a leading dense layer of a sparse model
        from ...parallel.moe import moe_mlp_nodrop

        stack, layer = experts or ({}, 0)
        return moe_mlp_nodrop({**p["moe"], **stack}, y, cfg, live, layer)
    return mlp_block(p["mlp"], y[None], cfg)[0], None


def _qkv(p, y, cfg, n):
    """Fused qkv projection over flat tokens [n, D] (+ optional biases,
    + the projection-wide QK-norm of ``cfg.qk_norm``). The weights lie
    ``[out, in]`` (:func:`serving_layout`)."""
    q = jnp.einsum("td,qd->tq", y, p["wq"])
    k = jnp.einsum("td,kd->tk", y, p["wk"])
    v = jnp.einsum("td,kd->tk", y, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q, k = qk_norm(p, q, k, cfg)
    k = scaled(k, cfg.mup.key)
    return (q.reshape(n, cfg.num_heads, cfg.head_dim),
            k.reshape(n, cfg.num_kv_heads, cfg.head_dim),
            v.reshape(n, cfg.num_kv_heads, cfg.head_dim))


def _attn_out(p, attn, cfg, n):
    if cfg.kv_lora_rank:
        return _mla_out(p, attn, cfg, n)
    # (a power-retention layer's output projection counts with its others)
    with scope("ret_proj") if cfg.retention_degree else nullcontext():
        out = jnp.einsum("tq,qd->td", attn.reshape(n, cfg.q_dim), p["wo"])
    if cfg.attn_out_bias:
        out = out + p["bo"].astype(out.dtype)
    return out


def _q_and_rows(p, y, cfg, positions, pos_embed=None):
    """What attention takes of flat tokens y [n, D]: the positioned queries
    [n, H, D_k] and the rows the pool caches of them, ``(k, v)`` [n, KVH, D]
    each, or for latent attention the one ``[c_kv | k_r]`` row [n, D_k];
    and third what a sparse-attention indexer reads (``dsa.index_rows``):
    the normed rows, or with latent attention's query latent beside them.
    ``pos_embed``: the layer's own (:class:`AttnKind`; None: the
    model's)."""
    if cfg.kv_lora_rank:
        # (an indexer that reads the query latent: made once for both)
        c_q = _mla_query_latent(p, y, cfg) if cfg.index_q_latent else None
        return (*_mla_rows(p, y, cfg, positions, c_q),
                y if c_q is None else (y, c_q))
    q, k, v = _qkv(p, y, cfg, y.shape[0])
    q, k = _positionize(cfg, q, k, positions, pos_embed or cfg.pos_embed)
    return q, (k, v), y


def _mla_query_latent(p, y, cfg):
    """``c_q = RMSNorm(y W_qa)`` [n, q_lora_rank]: what latent attention's
    queries are a projection of and, under ``cfg.index_q_latent``, a
    sparse-attention indexer's queries too."""
    with scope("mla_proj"):
        return rms_norm(y @ p["w_qa"], p["q_norm"]["scale"],
                        cfg.rms_norm_eps)


def _latent_layer(p, cfg):
    """A latent attention layer as :func:`_mla_rows` and :func:`_mla_out`
    read it: a layer of the serving tree as it is. One of the PUBLIC tree
    (told by ``w_kvb``'s name: ``tests/benchmark/test_xing4.py``, which holds
    the absorbed form against the expanded, hands them one) is laid out
    here."""
    return _lay(p, cfg, lambda x: x) if SPLIT in p else p


def _mla_rows(p, y, cfg, positions, c_q=None):
    """Latent attention (DeepSeek-V2's MLA) in ABSORBED form. The pool row
    is ``[RMSNorm(c_kv) | rope(k_r)]``, ``kv_lora_rank + qk_rope_head_dim``
    wide, one for all heads. Each head's query is laid against it:
    ``q_nope W_UK^T`` (the up-projection of keys folded into the query)
    beside its rotated part, so that ``q . row`` is the head's whole score.
    The value is the row's leading ``kv_lora_rank`` lanes; :func:`_mla_out`
    takes the attended latent up through ``W_UV``. The softmax scale
    (``cfg.softmax_scale``, YaRN's mscale squared in it) rides in q: every
    impl divides by sqrt of the row's width, so q carries that too.
    ``c_q``: the normed query latent where the caller made it already
    (:func:`_mla_query_latent`: a sparse-attention indexer reads it too)."""
    p = _latent_layer(p, cfg)
    n, h = y.shape[0], cfg.num_heads
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rot = lambda t: apply_rope(  # noqa: E731
        t[None], positions[None], cfg.rope_theta,
        scaling=cfg.rope_scaling)[0]
    with scope("mla_proj"):
        if c_q is None:
            c_q = rms_norm(y @ p["w_qa"], p["q_norm"]["scale"],
                           cfg.rms_norm_eps)
        # (w_qb lies [out, in]: serving_layout. The product ENDS here: seen
        # through the reshape, the v5e compiler lays 128 heads of 192 in
        # the lanes and regroups the weight by head for it, every forward)
        q = jax.lax.optimization_barrier(
            jnp.einsum("tc,qc->tq", c_q, p["w_qb"])).reshape(n, h, -1)
        ckv = y @ p["w_kva"]
        row = jnp.concatenate([
            rms_norm(ckv[:, :r], p["kv_norm"]["scale"], cfg.rms_norm_eps),
            rot(ckv[:, None, r:])[:, 0]], axis=-1)
        q_r = rot(q[..., nope:])
    with scope("mla_absorb"):
        q_lat = jnp.einsum("thn,hrn->thr", q[..., :nope], p["w_uk"],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_r.astype(jnp.float32)], axis=-1) \
            * (cfg.softmax_scale * np.sqrt(cfg.latent_kv_dim))
    return q.astype(y.dtype), (row,)


def _mla_out(p, attn, cfg, n):
    """attn [n, H, kv_lora_rank], the attended latents: up through each
    head's ``W_UV`` to [n, H, v_head_dim], then the output projection."""
    p = _latent_layer(p, cfg)
    with scope("mla_absorb"):
        out = jnp.einsum("thr,hvr->thv", attn, p["w_uv"])
    with scope("mla_proj"):
        return out.reshape(n, -1) @ p["wo"]


def _lane_pad(x, d_pad: int, is_q: bool = False):
    """Zero-pad the trailing head dim to the cache pool's lane-padded width
    (see ``kv_cache.lane_padded_head_dim``). Zero lanes cannot change q·k
    dot products, but every attention impl derives its softmax scale from
    the (padded) trailing dim — so q is pre-scaled by sqrt(d_pad/d), making
    scores/softmax mathematically identical to the unpadded computation (up
    to one fp rounding on q). The attention output is sliced back."""
    d = x.shape[-1]
    if d == d_pad:
        return x
    if is_q:
        x = x * np.sqrt(d_pad / d).astype(x.dtype)
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


def _positionize(cfg, q, k, positions, pos_embed):
    if pos_embed == "rope":
        q = apply_rope(q[None], positions[None], cfg.rope_theta,
                       cfg.rotary_dim)[0]
        k = apply_rope(k[None], positions[None], cfg.rope_theta,
                       cfg.rotary_dim)[0]
    return q, k


def _arch_bias(cfg):
    """The alibi slopes (None: the model has none); a layer's window is its
    :class:`AttnKind`'s."""
    return (jnp.asarray(alibi_slopes(cfg.num_heads) * cfg.alibi_scale)
            if cfg.pos_embed == "alibi" else None)


def _embed(params, tokens, sampled, take_from, positions, cfg):
    """The residual stream's first value (MFU region ``embed``): the rows
    of the table at the ids :func:`_tokens_in` chooses, the learned
    positions, the multipliers, the norm."""
    with region_scope("embed"):
        tokens = _tokens_in(tokens, sampled, take_from)
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
        if cfg.pos_embed == "learned":
            table = params["pos_embed"]["embedding"]
            pos = jnp.clip(positions + cfg.pos_embed_offset, 0,
                           table.shape[0] - 1)
            x = x + jnp.take(table, pos, axis=0).astype(x.dtype)
        if cfg.embed_scale != 1.0:
            x = x * cfg.embed_scale
        x = x.astype(jnp.dtype(cfg.dtype))
        if cfg.embed_norm:
            x = norm(x, params["embed_norm"], cfg)
        if cfg.hc_mult > 1:    # every residual stream starts as the embedding
            with scope("mhc"):
                x = jnp.broadcast_to(x, (cfg.hc_mult, *x.shape))
        return x


def _final_norm(params, x, cfg):
    with region_scope("head"):
        if cfg.hc_mult > 1:    # the streams close by their sum
            with scope("mhc"):
                x = x.astype(jnp.float32).sum(0).astype(x.dtype)
        return norm(x, params["final_norm"], cfg)


def _unembed(params, x, cfg):
    """Float32 logits of the normed rows x [S, d] (MFU region ``head``)."""
    with region_scope("head"), scope("lm_head"):
        if cfg.tie_embeddings:
            logits = jnp.einsum("sd,vd->sv", x,
                                params["embed"]["embedding"].astype(x.dtype))
        else:
            logits = jnp.einsum("sd,dv->sv", x,
                                params["lm_head"]["kernel"].astype(x.dtype))
            if cfg.lm_head_bias:
                logits = logits + params["lm_head"]["bias"].astype(
                    logits.dtype)
        return (logits if cfg.logit_scale == 1.0
                else logits * cfg.logit_scale).astype(jnp.float32)


def _block(cfg, p, x, attn_fn, live, experts=None):
    """One transformer block over flat tokens, covering sequential and
    parallel (GPT-J/NeoX/Falcon/Phi) residual forms. ``live`` [T]: the rows
    that are tokens, not padding; ``experts``: :func:`_mlp`'s. Returns
    (x, :func:`_mlp`'s expert rows). Each sublayer with its norm and its
    residual add is an MFU region (``attn``, ``mlp``)."""
    if cfg.hc_mult > 1:
        return _hc_block(cfg, p, x, attn_fn, live, experts)
    with region_scope("attn"):
        x_norm = norm(x, p["attn_norm"], cfg)
        attn = attn_fn(x_norm)
        h = _attn_out(p["attn"], attn, cfg, x.shape[0])
    if cfg.parallel_block:   # (the one sum of both branches: the MLP's)
        with region_scope("mlp"):
            y = x_norm if cfg.shared_block_norm \
                else norm(x, p["mlp_norm"], cfg)
            m, rows = _mlp(p, y, cfg, live, experts)
            return (x + h + m).astype(x.dtype), rows
    with region_scope("attn"):
        if cfg.sandwich_norm:    # the sublayer's OUTPUT is normed too
            h = norm(h, p["attn_post_norm"], cfg)
        x = (x + h).astype(x.dtype)
    with region_scope("mlp"):
        m, rows = _mlp(p, norm(x, p["mlp_norm"], cfg), cfg, live, experts)
        if cfg.sandwich_norm:
            m = norm(m, p["mlp_post_norm"], cfg)
        return (x + m).astype(x.dtype), rows


def _hc_maps(hc, x, cfg):
    """One sublayer's hyper-connection maps from the streams x [n, T, d]
    (mHC, arXiv:2512.24880): ``pre`` [n, T] and ``post`` [n, T], how the
    sublayer reads the streams and writes them, and ``res`` [n, n, T], how
    the streams mix: ``exp`` of the clamped map, then ``hc_sinkhorn_iters``
    rounds of columns, then rows, each divided by its sum + ``hc_eps``, so
    doubly stochastic to rounding. All from ONE RMSNorm over the token's
    ``n * d`` values and one [n*d, n + n + n*n] product, in float32; tokens
    ride the minor axis (a [T, 4, 4] would be tiled up to [T, 8, 128])."""
    n, t, d = x.shape
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=(0, 2), keepdims=True)
    xn = xf * jax.lax.rsqrt(var + cfg.rms_norm_eps) \
        * hc["norm"]["scale"].astype(jnp.float32).reshape(n, 1, d)
    a, b = hc["a"].astype(jnp.float32), hc["b"].astype(jnp.float32)
    maps = jnp.einsum("ntd,ndk->kt", xn,
                      hc["phi"].astype(jnp.float32).reshape(n, d, -1),
                      precision=jax.lax.Precision.HIGHEST)
    pre = jax.nn.sigmoid(a[0] * maps[:n] + b[:n, None])
    post = 2.0 * jax.nn.sigmoid(a[1] * maps[n:2 * n] + b[n:2 * n, None])
    res = jnp.exp(jnp.clip(a[2] * maps[2 * n:] + b[2 * n:, None],
                           cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)
                  ).reshape(n, n, t)                  # [row i, column j, T]
    for _ in range(cfg.hc_sinkhorn_iters):
        res = res / (res.sum(0, keepdims=True) + cfg.hc_eps)
        res = res / (res.sum(1, keepdims=True) + cfg.hc_eps)
    return pre, post, res


def _hc_block(cfg, p, x, attn_fn, live, experts=None):
    """:func:`_block` over ``hc_mult`` residual streams x [n, T, d]: each
    sublayer F (attention, then the MLP, each with maps of its own) reads
    ``u = pre . x``, and the streams become ``res x + post^T F(norm(u))``.
    Per token: nothing of it is cached."""
    def sublayer(x, hc, f):
        with scope("mhc"):
            pre, post, res = _hc_maps(hc, x, cfg)
            xf = x.astype(jnp.float32)
            u = (pre[:, :, None] * xf).sum(0).astype(x.dtype)
        y, rows = f(u)
        with scope("mhc"):
            x = ((res[:, :, :, None] * xf[None]).sum(1)
                 + post[:, :, None] * y.astype(jnp.float32)[None]
                 ).astype(x.dtype)
        return x, rows

    t = x.shape[1]
    # (a sublayer's maps and mixes count with the sublayer they are around)
    with region_scope("attn"):
        x, _ = sublayer(x, p["hc_attn"], lambda u: (_attn_out(
            p["attn"], attn_fn(norm(u, p["attn_norm"], cfg)), cfg, t), None))
    with region_scope("mlp"):
        return sublayer(x, p["hc_mlp"], lambda u: _mlp(
            p, norm(u, p["mlp_norm"], cfg), cfg, live, experts))


def _paged_attention(q, k_cache, v_cache, token_seq, token_pos, block_tables,
                     block_size: int, alibi=None, window=None, sel=None):
    """q: [T, H, D]; caches: [num_slots, KVH, D] (flat slot axis);
    block_tables: [S, Bps]. Returns [T, H, D]. ``sel`` [T, max_ctx]: a
    sparse-attention indexer's selection, nonzero where the row attends.

    Each token's query attends to its sequence's KV at positions <= its own.
    Per-sequence KV is materialized by resolving the block table to flat slot
    ids and gathering — O(S · max_ctx) memory, the XLA-correctness baseline the
    Pallas kernel will replace with true block-sparse streaming.
    """
    t, h, d = q.shape
    s, bps = block_tables.shape
    max_ctx = bps * block_size
    kvh = k_cache.shape[1]

    # seq-relative position j lives in flat slot table[j // bs] * bs + j % bs
    j = jnp.arange(max_ctx)
    slot_of_pos = block_tables[:, j // block_size] * block_size + (j % block_size)
    k_seq = k_cache[slot_of_pos]  # [S, max_ctx, KVH, D]
    v_seq = v_cache[slot_of_pos]

    seq_clip = jnp.minimum(token_seq, s - 1)  # padded tokens: any valid row
    k_tok = k_seq[seq_clip]  # [T, max_ctx, KVH, D]
    v_tok = v_seq[seq_clip]
    if kvh != h:
        rep = h // kvh
        k_tok = jnp.repeat(k_tok, rep, axis=2)
        v_tok = jnp.repeat(v_tok, rep, axis=2)

    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("thd,tchd->thc", q.astype(jnp.float32),
                        k_tok.astype(jnp.float32)) * scale
    if alibi is not None:
        logits = logits + alibi.astype(jnp.float32)[None, :, None] * (
            j[None, None, :] - token_pos[:, None, None]).astype(jnp.float32)
    mask = (j[None, :] <= token_pos[:, None])[:, None, :]  # causal over own seq
    if window is not None:
        mask = jnp.logical_and(
            mask, (token_pos[:, None] - j[None, :] < window)[:, None, :])
    if sel is not None:
        mask = jnp.logical_and(mask, (sel != 0)[:, None, :])
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("thc,tchd->thd", probs, v_tok.astype(jnp.float32))
    return out.astype(q.dtype)


def _packed_flash_attention(q, k_cache, v_cache, token_seq, token_pos,
                            block_tables, block_size: int, alibi=None,
                            window=None):
    """Chunked-prefill attention through the Pallas flash kernel.

    The fix for the O(T·max_ctx) per-token KV gather of
    :func:`_paged_attention`: KV is gathered once per SEQUENCE
    ([S, max_ctx] resolved from the block table), flattened into one packed
    stream with per-slot segment ids + positions, and the flat token
    queries attend through ``flash_attention``'s ragged cross-attention
    mode — per-sequence boundaries from q/kv segment ids, causality in
    position space, logits streamed (never materialized). This is the
    TTFT-critical path (reference ``blocked_flash`` over ragged atoms).
    """
    from ...ops.flash_attention import flash_attention

    t, h, d = q.shape
    s, bps = block_tables.shape
    bs = block_size
    max_ctx = bps * bs

    j = jnp.arange(max_ctx)
    slot_of_pos = block_tables[:, j // bs] * bs + (j % bs)
    k_seq = k_cache[slot_of_pos]  # [S, max_ctx, KVH, D] — once per sequence
    v_seq = v_cache[slot_of_pos]
    kvh = k_cache.shape[1]
    k_flat = k_seq.reshape(1, s * max_ctx, kvh, d)
    v_flat = v_seq.reshape(1, s * max_ctx, kvh, d)
    kv_seg = jnp.repeat(jnp.arange(s, dtype=jnp.int32), max_ctx)[None]
    kv_pos = jnp.tile(jnp.arange(max_ctx, dtype=jnp.int32), s)[None]
    # pad tokens carry token_seq == S, matching no kv segment → fully masked
    out = flash_attention(q[None], k_flat, v_flat, causal=True,
                          segment_ids=token_seq[None].astype(jnp.int32),
                          kv_segment_ids=kv_seg,
                          q_positions=token_pos[None].astype(jnp.int32),
                          kv_positions=kv_pos, alibi=alibi, window=window)
    return out[0]


def _layer_kv(ctx):
    """One layer's [num_slots, KVH, D] K and V for the impls that gather
    from it in XLA (CPU and parity tests; no cell runs them); of a latent
    pool, its rows as the one KV head and their leading lanes as V."""
    k = ctx.k_cache[ctx.layer]
    if ctx.v_cache is None:
        k = k[:, None]
        return k, k[..., :ctx.v_dim]
    return k, ctx.v_cache[ctx.layer]


# ------------------------------------------ registered prefill-attn impls
# (the reference's modules/implementations/* + heuristics, as registry
# entries; users can register_impl their own and name it in the config)
def _has_atoms(ctx):
    return bool(ctx.get("has_atoms"))


@register_impl("prefill_attn", "kernel", priority=10, available=_has_atoms,
               auto_eligible=lambda c: _has_atoms(c)
               and c.get("backend") == "tpu",
               metadata={"needs_atoms": True})
def _prefill_kernel_impl(q, ctx: PrefillAttnContext, interpret=False):
    """Ragged paged-attention Pallas kernel (arXiv:2604.15464; reference
    blocked_flash + atom_builder), the tile height following the chunk's
    length: TWO calls of the one kernel body on the same pool and layer.
    The rows of chunks of two tokens or more gather into fixed-size
    single-sequence atoms of ``atom_q_size`` rows; the one-token chunks
    (``dec_row`` / ``dec_len``, one per slot: the decoding sequences of a
    mixed round) go through :func:`paged_decode_attention`, the one-row
    tile ``decode_forward`` calls, and are scattered back to their packed
    rows. KV blocks stream via block-table DMA in both — the [S, max_ctx]
    HBM gather of the xla impl never happens."""
    from ...ops.paged_attention import (paged_decode_attention,
                                        ragged_prefill_attention)

    impl = "pallas_interpret" if interpret else "pallas"
    kw = dict(block_size=ctx.block_size, layer=ctx.layer, alibi=ctx.alibi,
              window=ctx.window, v_dim=ctx.v_dim, impl=impl)
    atoms_name, rows_name = _kernel_names(ctx.kind)
    q_at = q[ctx.atom_qidx]                          # [A, BQ, H, D]
    out_at = ragged_prefill_attention(
        q_at, ctx.k_cache, ctx.v_cache, ctx.atom_tables, ctx.atom_pos0,
        ctx.atom_qlen, **kw, **atoms_name)
    flat = out_at.reshape(-1, *out_at.shape[2:])
    out = flat[ctx.atom_inv]                         # back to packed rows
    out_dec = paged_decode_attention(                # [S, H, D]
        q[ctx.dec_row], ctx.k_cache, ctx.v_cache, ctx.block_tables,
        ctx.dec_len, **kw, **rows_name)
    # a slot with no one-token chunk scatters out of range (dropped)
    rows = jnp.where(ctx.dec_len > 0, ctx.dec_row, q.shape[0])
    return out.at[rows].set(out_dec, mode="drop")


@register_impl("prefill_attn", "kernel_interpret", priority=-10,
               available=_has_atoms, auto_eligible=lambda c: False,
               metadata={"needs_atoms": True})
def _prefill_kernel_interpret_impl(q, ctx: PrefillAttnContext):
    return _prefill_kernel_impl(q, ctx, interpret=True)


@register_impl("prefill_attn", "flash", priority=5,
               auto_eligible=lambda c: c.get("backend") == "tpu")
def _prefill_flash_impl(q, ctx: PrefillAttnContext):
    return _packed_flash_attention(q, *_layer_kv(ctx),
                                   ctx.token_seq, ctx.token_pos,
                                   ctx.block_tables, ctx.block_size,
                                   alibi=ctx.alibi, window=ctx.window)


@register_impl("prefill_attn", "xla", priority=0)
def _prefill_xla_impl(q, ctx: PrefillAttnContext):
    return _paged_attention(q, *_layer_kv(ctx), ctx.token_seq,
                            ctx.token_pos, ctx.block_tables, ctx.block_size,
                            alibi=ctx.alibi, window=ctx.window)


# decode_attn kind: one-token-per-slot steady state (the reference's
# blocked_flash decode path) — the same registry surface as prefill
def _decode_dispatch(impl_name):
    def fn(q, ctx):
        from ...ops.paged_attention import paged_decode_attention

        return paged_decode_attention(
            q, ctx.k_cache, ctx.v_cache, ctx.block_tables, ctx.seq_lens,
            block_size=ctx.block_size, impl=impl_name, layer=ctx.layer,
            alibi=ctx.alibi, window=ctx.window, v_dim=ctx.v_dim,
            **_kernel_names(ctx.kind)[1])
    return fn


class DecodeAttnContext(NamedTuple):
    """As :class:`PrefillAttnContext`: the whole pool and the layer."""
    k_cache: Any
    v_cache: Any
    layer: Any
    block_tables: Any
    seq_lens: Any
    block_size: int
    alibi: Any
    window: Optional[int]
    v_dim: Optional[int] = None
    kind: Optional[str] = None     # as PrefillAttnContext's


register_impl("decode_attn", "pallas", priority=10,
              auto_eligible=lambda c: c.get("backend") == "tpu")(
    _decode_dispatch("pallas"))
register_impl("decode_attn", "pallas_interpret", priority=-10,
              auto_eligible=lambda c: False)(
    _decode_dispatch("pallas_interpret"))
register_impl("decode_attn", "xla", priority=0)(_decode_dispatch("xla"))


# ssm_step / ret_step / kda_step kinds: a state layer's one-token update,
# Mamba-2's (``ops/ssm.py``), power retention's (``ops/retention.py``) and
# the delta rule's (``ops/kda.py``); conv_step: the one-token rows of the
# depthwise convolution before the first and the last (``ops/ssm.py``);
# conv_pieces: the pieces of the same convolution; kda_chunk: the delta
# rule's pieces (``ops/kda.py``): the in-place Pallas kernel on the TPU,
# gather/update/scatter (a loop of XLA pieces) elsewhere
def _state_dispatch(kind, impl_name):
    def fn(*args):
        from ...ops import kda, retention, ssm

        steps = {"ssm_step": ssm.STATE_STEPS,
                 "ret_step": retention.STATE_STEPS,
                 "kda_step": kda.STATE_STEPS,
                 "kda_chunk": kda.PIECES,
                 "conv_step": ssm.CONV_STEPS,
                 "conv_pieces": ssm.CONV_PIECES}[kind]
        return steps[impl_name](*args)
    return fn


for _kind in ("ssm_step", "ret_step", "kda_step", "kda_chunk", "conv_step",
              "conv_pieces"):
    register_impl(_kind, "pallas", priority=10,
                  auto_eligible=lambda c: c.get("backend") == "tpu")(
        _state_dispatch(_kind, "pallas"))
    register_impl(_kind, "pallas_interpret", priority=-10,
                  auto_eligible=lambda c: False)(
        _state_dispatch(_kind, "pallas_interpret"))
    register_impl(_kind, "xla", priority=0)(_state_dispatch(_kind, "xla"))


def _state_step_fn(kind):
    """The platform's state step: no setting names one (the registry is the
    seam a test or a third party puts another behind)."""
    return select_impl(kind, "auto", {"backend": jax.default_backend()}).fn


def _ssm_step_fn():
    return _state_step_fn("ssm_step")


def _conv_step_fn():
    return _state_step_fn("conv_step")


def _conv_pieces_fn():
    return _state_step_fn("conv_pieces")


def _ret_step_fn():
    return _state_step_fn("ret_step")


def _kda_step_fn():
    return _state_step_fn("kda_step")


def _kda_chunk_fn():
    return _state_step_fn("kda_chunk")


def _retention_rows(p, y, cfg, positions):
    """What a power-retention layer takes of flat tokens y [n, D]: the
    positioned queries [n, H, D_k], keys and values [n, KVH, D_k] (scope
    ``ret_proj``) and the log of each KV head's gate [n, KVH], float32
    (``ret_gate``)."""
    with scope("ret_proj"):
        q, (k, v), _ = _q_and_rows(p, y, cfg, positions)
    with scope("ret_gate"):
        gam = jax.nn.log_sigmoid(
            y.astype(jnp.float32) @ p["g_proj"].astype(jnp.float32)
            + p["g_bias"].astype(jnp.float32))
    return q, k, v, gam


def _pool_write(pools, layer, dest, rows):
    """Scatter the new tokens' rows (K and V [n, KVH, d] each; a latent
    pool's one [n, d]) into layer ``layer`` of ``pools`` at flat slots
    ``dest`` [n] (out-of-range = dropped). A scatter on the WHOLE
    loop-carried pool: XLA updates it in place, where a per-layer slice as
    the scan's xs/ys cost a slice, a copy and a write-back of the layer
    (157 MB at phi-2's pool) for 32 rows. A pool beyond ``rows`` (an
    indexer's keys: :func:`_index_write` writes them) passes through."""
    with jax.named_scope("kv_pool_write"):
        return tuple(
            pool.at[layer, dest].set(
                _lane_pad(new, pool.shape[-1]).astype(pool.dtype), mode="drop")
            for pool, new in zip(pools, rows)) + tuple(pools[len(rows):])


def _index_write(p_attn, y, cfg, positions, pools, layer, dest, mates):
    """A sparse-attention indexer's rows of the normed tokens y, or of them
    and the query latent (``dsa.index_rows``), its keys written into the
    pools' LAST array at the slots the attention's own rows went to
    (``mates``: ``dsa.pair_mates`` of the batch). -> (pools, (qI, w))."""
    from .dsa import index_pool_write, index_rows

    with jax.named_scope("dsa_index"):
        q_i, k_i, w = index_rows(p_attn, y, cfg, positions)
        pools = (*pools[:-1],
                 index_pool_write(pools[-1], layer, dest, k_i, mates))
    return pools, (q_i, w)


def _attn_views(cfg, pools):
    """``(k_cache, v_cache, v_dim, width of the output to keep)`` of the
    loop-carried ``pools`` for the attention contexts (a latent pool's (row,)
    or, beside a sparse-attention indexer, (row, idx): no V either way)."""
    if cfg.kv_lora_rank:
        return pools[0], None, cfg.kv_lora_rank, cfg.kv_lora_rank
    return pools[0], pools[1], None, cfg.head_dim


def _experts_in_place(layers, dtype):
    """Split the stacked ``layers`` into ``(what rides as the scan's xs, the
    routed experts' three leaves kept OUT of them)``, the second empty for
    a dense model. By the tree: an expert leaf stays out when it is a plain
    ``[L_moe, E, ., .]`` array of the activations' ``dtype``. Two kinds keep
    their per-layer slice, told by type and dtype: a ``QuantTensor`` (the
    layer is materialised by :func:`_dequant` anyway) and a leaf of another
    dtype (the cast would convert the whole stack every layer)."""
    from ...compression.quantize import QuantTensor

    moe = layers.get("moe", {})
    stack = {n: moe[n] for n in ("w_gate", "w_up", "w_down")
             if n in moe and not isinstance(moe[n], QuantTensor)
             and moe[n].ndim == 4 and moe[n].dtype == dtype}
    if not stack:
        return layers, stack
    rest = {n: w for n, w in moe.items() if n not in stack}
    return {**layers, "moe": rest}, stack


def moe_tile_rows(cfg, tokens: int) -> int:
    """Rows of the tiles a forward over ``tokens`` rows (its whole budget,
    pads included) lays each expert's (token, choice) rows in: the grouped
    GEMM's static choice by the shape (``ops.grouped_gemm.row_tile``)."""
    return row_tile(tokens * cfg.num_experts_per_tok, cfg.num_experts)


def _scan_layers(layer, x, kv: BlockedKV, params, cfg):
    """The layer loop of both serving forwards: the pool rides as CARRY
    beside ``x`` (never as the scan's xs/ys, which would slice it by layer
    and stack a second pool), the stacked params and the layer index as xs.
    A model with leading dense layers (``params["dense_layers"]``) walks
    that stack first, then the expert layers, one pool index through both.

    The routed experts' matrices are NOT among the xs
    (:func:`_experts_in_place`): their consumer, the grouped GEMM, is a
    custom call on the TPU and takes whole buffers, so the scan's slice of
    a layer's ``[E, ., .]`` was materialised before each read, three copies
    a layer that cost 1.56 x the grouped GEMMs they fed. The loop closes over
    the stacks instead (loop-invariant operands, as the pool is its carry)
    and hands ``layer`` the index WITHIN them, ``l - first``: one index for
    the pool, one for the expert stack. Router, shared expert, norms and
    attention stay in the xs: dense operands, whose slices fuse.

    A stack of several attention kinds (``cfg.attn_kinds``: a period of P
    layers, ``cfg.attn_period``) is scanned a PERIOD at a time: the xs are
    the stacked params seen as ``[periods, P, ...]`` (a reshape of the
    leading axis), and the period's layers are unrolled in the body, each
    handed its place ``j`` in the period, which is static and so are its
    window, its positions and its pool. The expert stack is still read in
    place, at ``period x P + j``. Every other model is a period of one
    layer: the same scan over the same xs.

    ``layer(carry, p, l, experts, j)`` returns ``(carry, expert rows [E] or
    None)``: a sparse-expert model's rows stack to [L_moe, E], over the
    router's whole width, and fold into ``kv.moe``; a program that holds a
    share of the experts (``kv.moe.rows``) counts ``touched`` and ``rows``
    over its own columns, ``cfg.held_experts``, and a pool that counts
    ``tiles`` gets the row tiles those rows fill (:func:`moe_tile_rows`).
    Returns ``(x, the new BlockedKV)``."""
    # (a uniform stack with recurrent state, power retention, carries it
    # behind its pools, which then have no rows)
    n_pools = len(kv.pools)
    carry, first = (x, kv.pools + kv.state), 0
    if "dense_layers" in params:
        dense = params["dense_layers"]
        first = jax.tree_util.tree_leaves(dense)[0].shape[0]
        carry, _ = jax.lax.scan(lambda c, inp: layer(c, *inp, None, 0),
                                carry, (dense, jnp.arange(first)))
    layers, stack = _experts_in_place(params["layers"], x.dtype)
    period = len(cfg.attn_kinds)
    if period == 1:
        def body(carry, inp):
            p, l = inp
            return layer(carry, p, l, (stack, l - first), 0)
    else:
        layers = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, period, *a.shape[1:]), layers)

        def body(carry, inp):
            p, l0 = inp
            rows = []
            for j in range(period):
                carry, r = layer(
                    carry, jax.tree_util.tree_map(lambda a: a[j], p),
                    l0 + j, (stack, l0 + j), j)
                rows.append(r)
            return carry, (None if rows[0] is None else jnp.stack(rows))

    (x, pools), rows = jax.lax.scan(
        body, carry, (layers, jnp.arange(first, cfg.num_layers)[::period]))
    if rows is not None and period > 1:
        rows = rows.reshape(-1, rows.shape[-1])
    return x, kv.with_pools(pools[:n_pools]).with_state(
        pools[n_pools:])._replace(
        moe=_count_moe(kv.moe, rows, cfg, x.shape[-2]))


def exit_choice(gate, h, threshold: float):
    """The exit rule of a looped stack over the passes' normed outputs h
    [U, S, d]: ``lam_u = sigmoid(h_u w + b)`` in float32, the exit
    distribution ``p_u = lam_u prod_{j<u} (1 - lam_j)`` (the last pass takes
    what is left), and a row's pass the first whose running sum of ``p``
    reaches ``threshold``, else the last. -> [S] int32."""
    lam = jax.nn.sigmoid(
        jnp.einsum("usd,do->us", h.astype(jnp.float32),
                   gate["kernel"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
        + gate["bias"].astype(jnp.float32))[:-1]            # [U - 1, S]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    reached = jnp.cumsum(lam * before, axis=0) >= threshold
    return jnp.where(reached.any(0), jnp.argmax(reached, axis=0),
                     h.shape[0] - 1).astype(jnp.int32)


def _scan_passes(layer, x, kv: BlockedKV, params, cfg, pick, live):
    """:func:`_scan_layers` for a looped stack (``cfg.total_ut_steps`` = U >
    1): an outer loop over the passes whose carry is ``(x, pools)``, inside
    it the layer scan over the SAME stacked params, two indices: layer ``l``
    of the weights, row ``u x L + l`` of the pool (``layer`` is handed the
    row: a pass writes and attends to its own keys and values). The final
    norm closes every pass and its output starts the next. Only the rows
    that are unembedded are kept of each pass (``pick(x) -> [S, d]``, so
    [U, S, d]); the exit gate and the rule (:func:`exit_choice`) choose each
    row's pass among them, and ``kv.exit_pass`` counts the ``live`` [S] rows
    by the pass chosen (all of it the MFU region ``head``'s, as every pass's
    final norm is). Every pass is computed whatever the rule says.
    Returns ``(the chosen normed rows [S, d], the new BlockedKV)``."""
    n, u_steps = cfg.num_layers, cfg.total_ut_steps

    def one_pass(carry, u):
        with jax.named_scope("loop_pass"):
            def body(carry, inp):
                p, l = inp
                return layer(carry, p, u * n + l, None, 0)[0], None

            (x, pools), _ = jax.lax.scan(
                body, carry, (params["layers"], jnp.arange(n)))
            x = _final_norm(params, x, cfg)
            with region_scope("head"):
                return (x, pools), pick(x)

    (_, pools), h = jax.lax.scan(one_pass, (x, kv.pools),
                                 jnp.arange(u_steps))
    with region_scope("head"), jax.named_scope("loop_exit"):
        chosen = exit_choice(params["exit_gate"], h,
                             cfg.early_exit_threshold)
        h_exit = jnp.take_along_axis(h, chosen[None, :, None], axis=0)[0]
        counted = kv.exit_pass + jnp.sum(
            (chosen[:, None] == jnp.arange(u_steps)) & live[:, None],
            axis=0, dtype=jnp.int32)
    return h_exit, kv.with_pools(pools)._replace(exit_pass=counted)


def _count_moe(moe, rows, cfg, tokens: int):
    """``kv.moe`` after a forward whose expert layers' routers gave ``rows``
    [L_moe, E] (None: a dense model, whose ``moe`` is None too)."""
    if rows is None:
        return moe
    with region_scope("mlp"):    # the experts' counters: their layers'
        load = moe.load + rows
        if moe.rows is not None:
            rows = rows[:, cfg.held_experts]
        return MoeCounters(
            load, jnp.sum(rows > 0, dtype=jnp.int32),
            None if moe.rows is None else jnp.sum(rows, dtype=jnp.int32),
            None if moe.tiles is None else tile_visits(
                rows, moe_tile_rows(cfg, tokens)))


def layer_plan(pattern: str):
    """``[(unit, repeats), ...]`` covering ``pattern`` left to right: where
    a run of layers repeats (``EM`` pairs between attention layers, a whole
    ``MEMEM*E`` period), the run is ONE ``lax.scan`` over its repeats, so
    that 26 layers do not compile as 26 bodies. Greedy: at each layer the
    (unit, repeats >= 2) that covers most, the shorter unit on a tie; else
    the layer alone."""
    plan, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for u in range(1, (len(pattern) - i) // 2 + 1):
            unit, reps = pattern[i:i + u], 1
            while pattern[i + reps * u:i + (reps + 1) * u] == unit:
                reps += 1
            if reps > 1 and u * reps > len(best[0]) * best[1]:
                best = (unit, reps)
        plan.append(best)
        i += len(best[0]) * best[1]
    return plan


def _mamba_write(cfg, p, y, ssm_fn):
    """What a Mamba-2 mixer writes of the normed rows y [T, d], the part an
    ``M`` and an ``H`` layer share: ``in_proj`` to ``[z | xBC | dt]`` (under
    muP each slice times its multiplier, ``cfg.mup_in_proj``), the
    recurrence (``ssm_fn(p, xbc, dt) -> y [T, d_inner]`` float32:
    convolution, scan and ``D``, against the state pool), the gate and its
    grouped norm, ``out_proj`` (times ``mup.ssm_out``)."""
    from ...ops.ssm import gated_norm

    di, c = cfg.ssm_d_inner, cfg.ssm_conv_dim
    with scope("ssm_proj"):
        zxbcdt = y @ p["in_proj"]
        if cfg.mup_in_proj is not None:
            zxbcdt = (zxbcdt.astype(jnp.float32)
                      * cfg.mup_in_proj).astype(y.dtype)
    out = ssm_fn(p, zxbcdt[:, di:di + c], zxbcdt[:, di + c:])
    with scope("ssm_gate"):
        u = gated_norm(out, zxbcdt[:, :di], p["gate_norm"]["scale"], cfg)
    with scope("ssm_proj"):
        return scaled(u.astype(y.dtype) @ p["out_proj"], cfg.mup.ssm_out)


def _mamba_mixer(cfg, p, x, ssm_fn):
    """One Mamba-2 layer over flat tokens x [T, d]: ``x +``
    :func:`_mamba_write` of its norm."""
    m = _mamba_write(cfg, p, norm(x, p["norm"], cfg), ssm_fn)
    with scope("ssm_proj"):
        return (x + m).astype(x.dtype)


def _kda_mixer(cfg, p, x, conv_fn, scan_fn):
    """One gated delta-rule layer over flat tokens x [T, d]: ``qkv_proj`` to
    ``[q | k | v]`` and the depthwise convolution over them (``conv_fn(p,
    qkv) -> [T, 3 x heads x dim]`` float32, against the tail in the state
    pool); an L2 norm a head on q (scaled) and k; the log-decay a key
    channel ``-exp(A_log) softplus(y f_a f_b + dt_bias)`` and ``beta =
    kda_beta_scale sigmoid(y b_proj)``; the recurrence (``scan_fn(q, k, v,
    g, beta) -> [T, heads, dim]`` float32, against the state); an RMS norm
    a head times the output gate ``sigmoid(y g_a g_b)``; ``o_proj``."""
    from ...ops.kda import l2norm

    f32 = jnp.float32
    n, h, d = x.shape[0], cfg.kda_num_heads, cfg.kda_head_dim
    heads = lambda t: t.astype(f32).reshape(n, h, d)         # noqa: E731
    y = norm(x, p["norm"], cfg)
    with scope("kda_proj"):
        qkv = y @ p["qkv_proj"]
        decay = (y @ p["f_a"]) @ p["f_b"]
        gate = (y @ p["g_a"]) @ p["g_b"]
        beta = y @ p["b_proj"]
    with scope("kda_conv"):
        qkv = conv_fn(p, qkv)
    with scope("kda_gate"):
        q, k, v = (heads(qkv[:, i * h * d:(i + 1) * h * d])
                   for i in range(3))
        q, k = l2norm(q) * d ** -0.5, l2norm(k)
        g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            heads(decay) + p["dt_bias"].astype(f32).reshape(h, d))
        beta = cfg.kda_beta_scale * jax.nn.sigmoid(beta.astype(f32))
    with scope("kda_scan"):
        out = scan_fn(q, k, v, g, beta)
    with scope("kda_gate"):
        out = out * jax.lax.rsqrt(
            jnp.mean(jnp.square(out), -1, keepdims=True) + cfg.rms_norm_eps) \
            * p["o_norm"]["scale"].astype(f32) * jax.nn.sigmoid(heads(gate))
    with scope("kda_proj"):
        return (x + out.reshape(n, -1).astype(x.dtype) @ p["o_proj"]
                ).astype(x.dtype)


def _branch(cfg, h):
    """A sublayer's write under muP's depth scaling (``cfg.residual_scale``;
    1.0: as it is)."""
    return h if cfg.residual_scale == 1.0 else h * cfg.residual_scale


def _lightning_mixer(cfg, p, x, positions, scan_fn):
    """One lightning linear-attention layer over flat tokens x [T, d]: q, k,
    v and the gate's row from the normed input; an RMS norm a head on q and
    k and the rotation over the whole head; the recurrence (``scan_fn(q, k,
    v) -> [T, heads, dim]`` float32, against the state; q scaled by
    ``dim^-1/2``); an RMS norm over all the heads' outputs together times
    ``sigmoid`` of the gate; ``wo``."""
    f32 = jnp.float32
    n, h, d = x.shape[0], cfg.lightning_heads, cfg.lightning_head_dim
    rot = lambda t: apply_rope(  # noqa: E731
        t[None], positions[None], cfg.rope_theta)[0]
    y = norm(x, p["norm"], cfg)
    with scope("la_proj"):
        # (q, k and v lie [out, in]: serving_layout)
        q, k, v = (jnp.einsum("td,qd->tq", y, p[w]) for w in QKV)
        z = y @ p["wz"]
    with scope("la_gate"):
        q = rot(rms_norm(q.reshape(n, h, d), p["q_norm"]["scale"],
                         cfg.rms_norm_eps))
        k = rot(rms_norm(k.reshape(n, h, d), p["k_norm"]["scale"],
                         cfg.rms_norm_eps))
        q = q.astype(f32) * d ** -0.5
    with scope("la_scan"):
        out = scan_fn(q, k.astype(f32), v.reshape(n, h, d).astype(f32))
    with scope("la_gate"):
        out = rms_norm(out.reshape(n, -1), p["o_norm"]["scale"],
                       cfg.rms_norm_eps) * jax.nn.sigmoid(z.astype(f32))
    with scope("la_proj"):
        return (x + _branch(cfg, out.astype(x.dtype) @ p["wo"])
                ).astype(x.dtype)


def _walk_pattern(cfg, params, x, kv: BlockedKV, attend, ssm_step, live,
                  kda=None, lightning=None):
    """The layer loop of both serving forwards for a ``cfg.layer_pattern``
    model: :func:`layer_plan`'s runs, each kind of layer indexing ITS stack
    of parameters and ITS cache (``attn_layers`` and the KV pool for ``*``,
    ``mamba_layers`` or ``kda_layers`` and the recurrent state for ``M`` or
    ``K``, ``lightning_layers`` and the state for ``L``, ``ffn_layers`` for
    ``F``, ``layers`` and the expert counters for ``E``; ``hybrid_layers``
    and BOTH caches at one index for ``H``). As in
    :func:`_scan_layers` the pools ride as carry and the routed experts'
    matrices stay closed over; the other leaves are read at the layer's
    (traced) index inside the loop body, which is what a scan's xs are.

    ``attend(p_attn, y, pools, l) -> (rows [T, H, D], pools)``,
    ``ssm_step(p, xbc, dt, state, l) -> (y [T, d_inner], state)`` and
    ``kda`` = ``(conv(p, qkv, state, l) -> (out, state), scan(q, k, v, g,
    beta, state, l) -> (out, state))`` and ``lightning`` = ``(positions,
    scan(q, k, v, state, l) -> (out, state))`` are the forward's own. Every
    layer is ``x + mixer(norm(x))``; an ``H`` layer's mixer is two, attention
    heads (scope ``h1_attn``) and a Mamba-2 mixer that read the SAME normed
    rows, each scaled by muP's multipliers (``cfg.mup``), summed into one
    residual add. A model whose attention reads selected
    blocks carries what it counts (``kv.bsa``) behind the pools, zeroed at
    the forward's start."""
    layers, stack = _experts_in_place(params.get("layers", {}), x.dtype)
    at = lambda tree, j: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a[j], tree)

    def one(kind, carry, j):
        # a layer is ONE mixer with its norm and its residual add: the
        # channel mixers (F, E) the MFU region mlp, every other kind attn
        with region_scope("mlp" if kind in "FE" else "attn"):
            return mix(kind, carry, j)

    def mix(kind, carry, j):
        x, pools, state = carry
        rows = None

        def ssm_fn(p, xbc, dt):
            nonlocal state
            y, state = ssm_step(p, xbc, dt, state, j)
            return y

        if kind == "M":
            x = _mamba_mixer(cfg, at(params["mamba_layers"], j), x, ssm_fn)
        elif kind == "H":
            p = at(params["hybrid_layers"], j)
            y = norm(x, p["norm"], cfg)
            with scope("h1_attn"):
                rows_attn, pools = attend(
                    p["attn"], scaled(y, cfg.mup.attention_in), pools, j)
                a = scaled(_attn_out(p["attn"], rows_attn, cfg, x.shape[0]),
                           cfg.mup.attention_out)
            m = _mamba_write(cfg, p["mamba"], y, ssm_fn)
            x = (x + _branch(cfg, a + m)).astype(x.dtype)
        elif kind == "K":
            def conv_fn(p, qkv):
                nonlocal state
                out, state = kda[0](p, qkv, state, j)
                return out

            def scan_fn(*rows):
                nonlocal state
                out, state = kda[1](*rows, state, j)
                return out

            x = _kda_mixer(cfg, at(params["kda_layers"], j), x, conv_fn,
                           scan_fn)
        elif kind == "L":
            def la_fn(*rows):
                nonlocal state
                out, state = lightning[1](*rows, state, j)
                return out

            x = _lightning_mixer(cfg, at(params["lightning_layers"], j), x,
                                 lightning[0], la_fn)
        elif kind == "F":
            p = at(params["ffn_layers"], j)
            m, _ = _mlp(p, norm(x, p["mlp_norm"], cfg), cfg, live)
            x = (x + _branch(cfg, m)).astype(x.dtype)
        elif kind == "*":
            p = at(params["attn_layers"], j)
            y = norm(x, p["attn_norm"], cfg)
            rows_attn, pools = attend(p["attn"], y, pools, j)
            if cfg.attn_out_gate:
                with scope("attn_gate"):
                    open_ = jax.nn.sigmoid(
                        (y @ p["attn"]["w_g"]).astype(jnp.float32))
                    rows_attn = (rows_attn.astype(jnp.float32)
                                 * open_.reshape(rows_attn.shape)
                                 ).astype(rows_attn.dtype)
            x = (x + _branch(cfg, _attn_out(p["attn"], rows_attn, cfg,
                                            x.shape[0]))).astype(x.dtype)
        else:
            p = _dequant(at(layers, j), x.dtype, cfg)
            m, rows = _mlp(p, norm(x, p["mlp_norm"], cfg), cfg, live,
                           (stack, j))
            x = (x + _branch(cfg, m)).astype(x.dtype)
        return (x, pools, state), rows

    n_pools = len(kv.pools)
    counts = () if kv.bsa is None else (jnp.zeros_like(kv.bsa),)
    carry, done, routed = (x, kv.pools + counts, kv.state), \
        dict.fromkeys("MKLEFH*", 0), []
    for unit, reps in layer_plan(cfg.layer_pattern):
        per = {kind: unit.count(kind) for kind in done}

        def body(carry, i, unit=unit, per=per, base=dict(done)):
            at_kind = {kind: base[kind] + i * per[kind] for kind in per}
            rows = []
            for kind in unit:
                carry, r = one(kind, carry, at_kind[kind])
                at_kind[kind] += 1
                rows += [] if r is None else [r]
            return carry, (jnp.stack(rows) if rows else None)

        if reps == 1:
            carry, rows = body(carry, 0)
        else:
            carry, rows = jax.lax.scan(body, carry, jnp.arange(reps))
            rows = None if rows is None else rows.reshape(-1, rows.shape[-1])
        routed += [] if rows is None else [rows]
        for kind in done:
            done[kind] += reps * per[kind]
    x, pools, state = carry
    with region_scope("mlp"):
        routed = jnp.concatenate(routed) if routed else None
    moe = _count_moe(kv.moe, routed, cfg, x.shape[-2])
    kv = kv.with_pools(pools[:n_pools]).with_state(state)._replace(moe=moe)
    return x, kv._replace(bsa=pools[n_pools]) if counts else kv


def _tokens_in(tokens, sampled, take_from):
    """The token ids a forward embeds. ``take_from[i] >= 0`` says that row
    ``i``'s token is row ``take_from[i]`` of ``sampled``, the sampler's
    output over the LAST forward's logits, which the host has launched and
    not read (``engine_v2.SampledTokens``); every other row's is
    ``tokens[i]``, which the host wrote. Selected here, inside the forward:
    the host need not know a decode token to launch the forward that eats
    it. Without ``sampled`` every token is the host's."""
    if sampled is None:
        return tokens
    return jnp.where(take_from >= 0, sampled[jnp.maximum(take_from, 0)],
                     tokens)


def ragged_forward(model, params: Any, kv: BlockedKV, tokens, token_seq,
                   token_pos, block_tables, last_tok_idx,
                   atom_qidx=None, atom_pos0=None, atom_qlen=None,
                   atom_tables=None, atom_inv=None, dec_row=None,
                   dec_len=None, sampled=None, take_from=None, ssm=None,
                   window_tables=None, atom_window_tables=None, *,
                   block_size: int, attn_impl: str = "auto"
                   ) -> Tuple[jnp.ndarray, BlockedKV]:
    """Flat-token forward. Returns (per-slot last-token logits [S, V], new kv).

    ``model``: a ``models.CausalLM`` — its stacked-layer params drive a
    ``lax.scan`` here exactly as in training (``models/transformer.py``).
    ``atom_*`` and ``dec_*`` are ``RaggedBatch.tile_args``, what the
    ``kernel`` attention takes (the others route by ``token_seq`` alone).
    ``sampled`` / ``take_from`` [T]: :func:`_tokens_in`. ``ssm``: a model
    with recurrent state's ``ragged.SsmBatch`` (which state slot each
    chunk's sequence has, the one-token chunks, the pieces of the longer
    ones), for Mamba-2 and power-retention layers alike.
    ``window_tables`` [S, Bps] / ``atom_window_tables`` [A, Bps]: a stack
    of two attention kinds' tables into its windowed layers' pool, as
    ``block_tables`` / ``atom_tables`` are into the full layers'.
    """
    cfg = model.config
    assert cfg.scan_layers, "ragged engine requires scan_layers param layout"
    bs = block_size
    t = tokens.shape[0]
    s = block_tables.shape[0]
    ab = _arch_bias(cfg)

    pad = token_seq >= s  # padding sentinel from RaggedBatch

    def dest_in(tables, num_slots):
        """Flat destination slot per token under ``tables``; padded tokens
        scatter out-of-range (drop)."""
        block = tables[jnp.minimum(token_seq, s - 1), token_pos // bs]
        return jnp.where(pad, num_slots, block * bs + token_pos % bs)

    # (where every layer's mixer writes and whom it reads beside: the
    # mixers', computed once)
    with region_scope("attn"):
        dest = dest_in(block_tables, kv.num_slots)
        dest_w = None if window_tables is None \
            else dest_in(window_tables, kv.window_slots)

    x = _embed(params, tokens, sampled, take_from, token_pos, cfg)
    if cfg.index_topk:    # which rows share a row of the indexer's pool
        from .dsa import pair_mates

        with region_scope("attn"):
            mates = pair_mates(token_seq, token_pos, ~pad)

    def attend(p_attn, y, pools, l, j=0):
        """Layer ``l``'s attention over the normed rows y: the new rows into
        the pool, then every row against its sequence's cached context.
        ``j``: the layer's place in the period of attention kinds (static).
        -> (rows [T, H, D], pools)."""
        # resolved through the pluggable registry (module_registry.py — the
        # reference's module_registry + heuristics seam). Static per trace:
        # atom presence and backend are trace-time constants.
        if cfg.retention_degree:
            return retain(p_attn, y, pools, l)
        spec = select_impl("prefill_attn", attn_impl, {
            "backend": jax.default_backend(),
            "has_atoms": atom_qidx is not None,
        })
        # a stack of two attention kinds: the layer's kind has pools, tables
        # and a row of its own; every other model's one kind has them all
        kind = AttnKind.of(cfg, j)
        before, mine, after = kind.split(pools)
        row = kind.row(l)
        tables, tile_tables, to = (window_tables, atom_window_tables, dest_w) \
            if kind.windowed else (block_tables, atom_tables, dest)
        with kind.scope():
            q, new, y_idx = _q_and_rows(p_attn, y, cfg, token_pos,
                                        kind.pos_embed)
            mine = _pool_write(mine, row, to, new)
            q = _lane_pad(q, mine[0].shape[-1], is_q=True)
            k_cache, v_cache, v_dim, keep = _attn_views(cfg, mine)
            ctx = PrefillAttnContext(
                k_cache=k_cache, v_cache=v_cache, layer=row,
                token_seq=token_seq,
                token_pos=token_pos, block_tables=tables,
                block_size=bs, alibi=ab, window=kind.window,
                atom_qidx=atom_qidx, atom_pos0=atom_pos0,
                atom_qlen=atom_qlen, atom_tables=tile_tables,
                atom_inv=atom_inv, dec_row=dec_row, dec_len=dec_len,
                v_dim=v_dim, kind=kind.label)
            if cfg.index_topk:    # attention over the indexer's selection
                from .dsa import ragged_attend

                mine, idx_rows = _index_write(p_attn, y_idx, cfg, token_pos,
                                              mine, row, to, mates)
                return ragged_attend(q, *idx_rows, mine, row, ctx, cfg,
                                     spec.name)[..., :keep], mine
            if cfg.sparse_block_topk:    # ... over the selected blocks
                from .bsa import ragged_attend

                out, mine = ragged_attend(q, mine, row, ctx, cfg, spec.name)
                return out[..., :keep], mine
            out = spec.fn(q, ctx)[..., :keep]
        return out, (*before, *mine, *after)

    def retain(p_attn, y, pools, l):
        """Layer ``l``'s power retention over the normed rows y, in the
        place of attention: the pieces of the chunks of two tokens or more
        through the chunked form, the one-token chunks through the decode
        step, each against ITS slot's state, which is the carry's last two
        leaves. -> (rows [T, H, D], pools)."""
        from ...ops.retention import chunked, decode_step

        q, k, v, gam = _retention_rows(p_attn, y, cfg, token_pos)
        out, state = chunked(
            q, k, v, gam, pools[-2:], l,
            (ssm.row0, ssm.length, ssm.slot, ssm.fresh, ssm.count), cfg)
        one, at = ssm.dec_len > 0, ssm.dec_row
        with scope("ret_scan"):
            out_dec, state = decode_step(
                q[at], k[at], v[at], gam[at], state, l,
                jnp.where(one, ssm.seq_slot, state[0].shape[1] - 1),
                ssm.dec_len == 1, cfg, _ret_step_fn())
        # a slot with no one-token chunk scatters out of range (dropped)
        out = out.at[jnp.where(one, at, t)].set(out_dec, mode="drop")
        return out.astype(y.dtype), (*pools[:-2], *state)

    def layer(carry, p, l, experts, j):
        x, pools = carry
        p = _dequant(p, x.dtype, cfg)

        def attn_fn(y):
            nonlocal pools
            out, pools = attend(p["attn"], y, pools, l, j)
            return out

        with region_scope("mlp"):    # the rows the experts count
            live = ~pad
        x, rows = _block(cfg, p, x, attn_fn, live, experts)
        return (x, pools), rows

    def ssm_step(p, xbc, dt, state, l):
        """Mamba layer ``l``'s recurrence over the flat batch: the pieces of
        the chunks of two tokens or more through the chunked scan, the
        one-token chunks through the decode step (as their attention goes
        through the one-row tile), each from ITS slot's state."""
        from ...ops.ssm import chunked_scan, decode_step

        y, *state = chunked_scan(
            xbc, dt, p, *state, l,
            (ssm.row0, ssm.length, ssm.slot, ssm.fresh, ssm.count), cfg)
        one = ssm.dec_len > 0
        y_dec, *state = decode_step(
            xbc[ssm.dec_row], dt[ssm.dec_row], p, *state, l,
            jnp.where(one, ssm.seq_slot, state[0].shape[1] - 1),
            ssm.dec_len == 1, cfg, _ssm_step_fn(), _conv_step_fn())
        # a slot with no one-token chunk scatters out of range (dropped)
        y = y.at[jnp.where(one, ssm.dec_row, t)].set(y_dec, mode="drop")
        return y, tuple(state)

    def kda_conv(p, qkv, state, l):
        """Delta-rule layer ``l``'s convolution over the flat batch, as
        :func:`ssm_step` splits it: the pieces, then the one-token rows."""
        from ...ops.ssm import conv_pieces, conv_step

        w = p["conv_w"].astype(jnp.float32)
        out, conv = conv_pieces(
            qkv, w, None, state[1], l,
            (ssm.row0, ssm.length, ssm.slot, ssm.fresh, ssm.count),
            cfg.kda_chunk_size, _conv_pieces_fn())
        one = ssm.dec_len > 0
        out_dec, conv = conv_step(
            qkv[ssm.dec_row], w, None, conv, l,
            jnp.where(one, ssm.seq_slot, conv.shape[2] - 1),
            ssm.dec_len != 1, _conv_step_fn())
        out = out.at[jnp.where(one, ssm.dec_row, t)].set(out_dec,
                                                         mode="drop")
        return out, (state[0], conv)

    def kda_scan(q, k, v, g, beta, state, l):
        """... and its recurrence: the pieces through the chunked form, the
        one-token rows through the decode step, each from ITS slot."""
        from ...ops.kda import chunked, decode_step

        out, pool = chunked(
            q, k, v, g, beta, state[0], l,
            (ssm.row0, ssm.length, ssm.slot, ssm.fresh, ssm.count), cfg,
            _kda_chunk_fn())
        one, at = ssm.dec_len > 0, ssm.dec_row
        out_dec, pool = decode_step(
            q[at], k[at], v[at], g[at], beta[at], pool, l,
            jnp.where(one, ssm.seq_slot, pool.shape[1] - 1),
            ssm.dec_len == 1, cfg, _kda_step_fn())
        out = out.at[jnp.where(one, at, t)].set(out_dec, mode="drop")
        return out, (pool, state[1])

    def la_scan(q, k, v, state, l):
        """Lightning layer ``l``'s recurrence over the flat batch, as
        :func:`ssm_step` splits it: the pieces, then the one-token rows."""
        from ...ops.ssm import lightning_pieces, lightning_step

        out, pool = lightning_pieces(
            q, k, v, state[0], l,
            (ssm.row0, ssm.length, ssm.slot, ssm.fresh, ssm.count),
            cfg.lightning_chunk_size, x.dtype)
        one, at = ssm.dec_len > 0, ssm.dec_row
        out_dec, pool = lightning_step(
            q[at], k[at], v[at], pool, l,
            jnp.where(one, ssm.seq_slot, pool.shape[1] - 1),
            ssm.dec_len == 1, _ssm_step_fn())
        out = out.at[jnp.where(one, at, t)].set(out_dec, mode="drop")
        return out, (pool,)

    if cfg.total_ut_steps > 1:
        # a slot's row is a sequence's where the batch has a chunk of it
        with region_scope("head"):    # (the rows the exit counts)
            in_batch = token_seq[last_tok_idx] == jnp.arange(s)
        h_last, kv = _scan_passes(
            layer, x, kv, params, cfg, lambda x: x[last_tok_idx], in_batch)
        return _unembed(params, h_last, cfg), kv
    if cfg.layer_pattern is not None:
        with region_scope("mlp"):    # the rows the experts count
            live = ~pad
        x, kv = _walk_pattern(cfg, params, x, kv, attend, ssm_step, live,
                              (kda_conv, kda_scan), (token_pos, la_scan))
    else:
        x, kv = _scan_layers(layer, x, kv, params, cfg)

    x = _final_norm(params, x, cfg)
    with region_scope("head"):
        h_last = x[last_tok_idx]  # [S, d] — logits_gather
    return _unembed(params, h_last, cfg), kv


def _jit_program(name: str, fn, model, **static):
    """``fn(model, params, kv, ...)`` jitted with the pool donated, under
    the name the engine dispatches it by: a ``partial`` has no ``__name__``
    and would reach the profiler as ``jit__unknown``; this way the device
    trace's module reads ``jit_<name>(<hash>)`` and the host's span
    ``PjitFunction(<name>)``."""
    def program(*args):
        return fn(model, *args, **static)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, donate_argnums=(1,))


def build_ragged_forward_fn(model, block_size: int, attn_impl: str = "auto"):
    """Jitted, shape-stable forward (compiled once per engine)."""
    return _jit_program("ragged_forward", ragged_forward, model,
                        block_size=block_size, attn_impl=attn_impl)


# ------------------------------------------------------------ decode fast path
def decode_forward(model, params: Any, kv: BlockedKV, tokens, positions,
                   block_tables, active, sampled=None, take_from=None,
                   state_slot=None, window_tables=None, *,
                   block_size: int, attn_impl: str = "auto"
                   ) -> Tuple[jnp.ndarray, BlockedKV]:
    """All-decode forward: ONE token per slot, attention via the Pallas paged
    decode kernel (``ops/paged_attention`` — the ``blocked_flash`` analog).

    ``tokens``/``positions``/``active``: [S]; positions = tokens already
    cached (the new token writes slot ``positions[s]``). This is the program
    serving spends most of its life in, so it gets the kernel; mixed
    prefill+decode batches take :func:`ragged_forward`.
    ``sampled`` / ``take_from`` [S]: :func:`_tokens_in`. ``state_slot``
    [S]: a model with recurrent state's slot of each row's
    sequence (rows change place from forward to forward; a state does not).
    ``window_tables`` [S, Bps]: :func:`ragged_forward`'s.
    """
    cfg = model.config
    bs = block_size
    s = tokens.shape[0]
    ab = _arch_bias(cfg)

    def dest_in(tables, num_slots):
        block = jnp.take_along_axis(
            tables, (positions // bs)[:, None], axis=1)[:, 0]
        return jnp.where(active, block * bs + positions % bs, num_slots)

    with region_scope("attn"):    # as ragged_forward's
        dest = dest_in(block_tables, kv.num_slots)
        dest_w = None if window_tables is None \
            else dest_in(window_tables, kv.window_slots)
        seq_lens = jnp.where(active, positions + 1, 0)

    x = _embed(params, tokens, sampled, take_from, positions, cfg)

    def attend(p_attn, y, pools, l, j=0):
        if cfg.retention_degree:    # the state step in the place of attention
            from ...ops.retention import decode_step

            q, k, v, gam = _retention_rows(p_attn, y, cfg, positions)
            with scope("ret_scan"):
                out, state = decode_step(
                    q, k, v, gam, pools[-2:], l,
                    jnp.where(active, state_slot, pools[-2].shape[1] - 1),
                    positions == 0, cfg, _ret_step_fn())
            return out.astype(y.dtype), (*pools[:-2], *state)
        spec = select_impl("decode_attn", attn_impl,
                           {"backend": jax.default_backend()})
        kind = AttnKind.of(cfg, j)     # as ragged_forward's attend
        before, mine, after = kind.split(pools)
        row = kind.row(l)
        tables, to = (window_tables, dest_w) if kind.windowed \
            else (block_tables, dest)
        with kind.scope():
            q, new, y_idx = _q_and_rows(p_attn, y, cfg, positions,
                                        kind.pos_embed)
            mine = _pool_write(mine, row, to, new)
            q = _lane_pad(q, mine[0].shape[-1], is_q=True)
            k_cache, v_cache, v_dim, keep = _attn_views(cfg, mine)
            if cfg.index_topk:    # attention over the indexer's selection
                from .dsa import decode_attend

                # (no mates: the token beside a row's is never a row here)
                mine, idx_rows = _index_write(
                    p_attn, y_idx, cfg, positions, mine, row, to,
                    jnp.full((s,), -1, jnp.int32))
                return decode_attend(q, *idx_rows, mine, row, tables,
                                     seq_lens, bs, cfg,
                                     spec.name)[..., :keep], mine
            if cfg.sparse_block_topk:    # ... over the selected blocks
                from .bsa import decode_attend

                out, mine = decode_attend(q, mine, row, tables, seq_lens, bs,
                                          cfg, spec.name)
                return out[..., :keep], mine
            out = spec.fn(q, DecodeAttnContext(
                k_cache=k_cache, v_cache=v_cache, layer=row,
                block_tables=tables, seq_lens=seq_lens, block_size=bs,
                alibi=ab, window=kind.window, v_dim=v_dim,
                kind=kind.label))[..., :keep]
        return out, (*before, *mine, *after)

    def layer(carry, p, l, experts, j):
        x, pools = carry
        p = _dequant(p, x.dtype, cfg)

        def attn_fn(y):
            nonlocal pools
            out, pools = attend(p["attn"], y, pools, l, j)
            return out

        x, rows = _block(cfg, p, x, attn_fn, active, experts)
        return (x, pools), rows

    def ssm_step(p, xbc, dt, state, l):
        from ...ops.ssm import decode_step

        y, *state = decode_step(
            xbc, dt, p, *state, l,
            jnp.where(active, state_slot, state[0].shape[1] - 1),
            positions == 0, cfg, _ssm_step_fn(), _conv_step_fn())
        return y, tuple(state)

    def kda_conv(p, qkv, state, l):
        from ...ops.ssm import conv_step

        out, conv = conv_step(
            qkv, p["conv_w"].astype(jnp.float32), None, state[1], l,
            jnp.where(active, state_slot, state[1].shape[2] - 1),
            positions != 0, _conv_step_fn())
        return out, (state[0], conv)

    def kda_scan(q, k, v, g, beta, state, l):
        from ...ops.kda import decode_step

        out, pool = decode_step(
            q, k, v, g, beta, state[0], l,
            jnp.where(active, state_slot, state[0].shape[1] - 1),
            positions == 0, cfg, _kda_step_fn())
        return out, (pool, state[1])

    def la_scan(q, k, v, state, l):
        from ...ops.ssm import lightning_step

        out, pool = lightning_step(
            q, k, v, state[0], l,
            jnp.where(active, state_slot, state[0].shape[1] - 1),
            positions == 0, _ssm_step_fn())
        return out, (pool,)

    if cfg.total_ut_steps > 1:
        x, kv = _scan_passes(layer, x, kv, params, cfg, lambda x: x, active)
        return _unembed(params, x, cfg), kv
    if cfg.layer_pattern is not None:
        x, kv = _walk_pattern(cfg, params, x, kv, attend, ssm_step, active,
                              (kda_conv, kda_scan), (positions, la_scan))
    else:
        x, kv = _scan_layers(layer, x, kv, params, cfg)
    return _unembed(params, _final_norm(params, x, cfg), cfg), kv


def build_decode_forward_fn(model, block_size: int, attn_impl: str = "auto"):
    return _jit_program("decode_forward", decode_forward, model,
                        block_size=block_size, attn_impl=attn_impl)
