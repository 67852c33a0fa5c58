"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

TPU-native rebuild of ``deepspeed/moe/`` (SURVEY.md §2.4 EP row):

* gating — ``TopKGate`` / ``top1gating`` / ``top2gating``
  (``moe/sharded_moe.py:348,184,282``): router logits → top-k experts, capacity
  truncation, load-balance aux loss ``E * Σ_e (mean_prob_e × token_frac_e)``.
* dispatch — the reference routes tokens with an explicit ``_AllToAll`` autograd op
  (``moe/sharded_moe.py:95``) between expert-parallel ranks. Here dispatch/combine
  are einsums against a one-hot capacity layout; with experts sharded over the
  ``expert`` axis and tokens over (data, fsdp), XLA lowers those einsums to exactly
  the all-to-all pair over ICI — no hand-written comm.
* expert compute — vmapped GLU over the expert dim (the grouped-GEMM the reference
  gets from CUTLASS, ``inference/v2/.../cutlass_multi_gemm.py``; on TPU the batched
  einsum hits the MXU directly).

Shapes: T tokens, E experts, C capacity, D model, F ffn.
"""
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..models.layers import _activation, constrain, glu_mlp, std_mlp


def topk_weights(probs: jnp.ndarray, k: int, normalise: bool,
                 select_bias: Optional[jnp.ndarray] = None,
                 scale: float = 1.0,
                 groups: Optional[Tuple[int, int]] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The ONE top-k weighting of both MoE paths: the ``k`` largest router
    scores of each token [T, k] and their experts [T, k]; divided by their
    sum where the model says so (``cfg.norm_topk_prob``: mixtral does, OLMoE
    combines with the raw softmax mass). ``select_bias`` [E] (``noaux_tc``)
    joins the scores for the CHOICE only: the weights are the chosen
    experts' unbiased scores. ``scale``: ``cfg.routed_scaling_factor``.
    ``groups`` ``(n_group, topk_group)`` (``group_limited_greedy``): the
    experts lie in ``n_group`` equal runs of ids; a token keeps the
    ``topk_group`` groups whose BEST score is highest, the scores of every
    other group are set to 0, and the ``k`` are taken of what is left (ties
    go to the lower id, among groups as among experts)."""
    if groups is not None:
        n_group, topk_group = groups
        t, e = probs.shape
        best = probs.reshape(t, n_group, e // n_group).max(-1)      # [T, G]
        _, kept = jax.lax.top_k(best, topk_group)
        kept = jax.nn.one_hot(kept, n_group, dtype=jnp.bool_).any(1)
        probs = jnp.where(jnp.repeat(kept, e // n_group, axis=1), probs, 0.0)
    if select_bias is None:
        gate_w, expert_idx = jax.lax.top_k(probs, k)
    else:
        _, expert_idx = jax.lax.top_k(probs + select_bias, k)
        gate_w = jnp.take_along_axis(probs, expert_idx, axis=-1)
    if normalise:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return gate_w if scale == 1.0 else gate_w * scale, expert_idx


def router_scores(logits: jnp.ndarray, cfg) -> jnp.ndarray:
    """Router scores [T, E] in float32: a softmax over the experts, or
    (``scoring_func: sigmoid``) each expert's own sigmoid."""
    logits = logits.astype(jnp.float32)
    if cfg.scoring_func == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def topk_gating(logits: jnp.ndarray, k: int, capacity: int,
                rng: Optional[jax.Array] = None,
                jitter: float = 0.0, normalise: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k gating with capacity (reference ``top1gating``/``top2gating``,
    ``moe/sharded_moe.py:184,282``).

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights, aux_loss).
    """
    t, e = logits.shape
    if jitter > 0.0 and rng is not None:
        logits = logits * jax.random.uniform(
            rng, logits.shape, logits.dtype, 1.0 - jitter, 1.0 + jitter)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]

    # top-k expert ids per token, and their (re)normalised weights
    gate_w, expert_idx = topk_weights(probs, k, normalise)        # [T, k]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)     # [T, k, E]

    # Load-balance aux loss (top2gating: uses the top-1 assignment fraction).
    me = probs.mean(axis=0)                                       # [E]
    ce = onehot[:, 0, :].mean(axis=0)                             # [E]
    aux_loss = jnp.sum(me * ce) * e

    # Position of each (token, choice) within its expert's capacity buffer.
    # Flatten choices in priority order: all top-1 choices first (they win capacity
    # slots over top-2 spill), matching the reference's top-2 ordering.
    flat = onehot.transpose(1, 0, 2).reshape(k * t, e)            # [k*T, E]
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat               # [k*T, E]
    within = (pos_in_expert < capacity)
    flat = flat * within
    pos = (pos_in_expert * flat).sum(axis=-1)                     # [k*T]
    keep = flat.sum(axis=-1)                                      # [k*T] 0/1

    gate_flat = gate_w.transpose(1, 0).reshape(k * t) * keep      # [k*T]

    cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32)               # [k*T, C]
    # [k*T, E, C] → sum over choices → [T, E, C]
    dc = flat[:, :, None] * cap_onehot[:, None, :]
    dispatch = dc.reshape(k, t, e, capacity).sum(axis=0)
    combine = (gate_flat[:, None, None] * dc).reshape(
        k, t, e, capacity).sum(axis=0)
    return dispatch, combine, aux_loss


def _expert_form(cfg):
    """``(gated, activation)`` of an expert, on both paths: ``mlp_type``
    ``"glu"`` is three matrices, ``act(x w_gate) * (x w_up)`` then ``w_down``
    (silu, else gelu, as it always was); ``"mlp"`` is two and no gate,
    ``act(x w_up) w_down`` with the model's own activation, and
    ``init_params`` draws no ``w_gate``."""
    if cfg.mlp_type == "glu":
        return True, (jax.nn.silu if cfg.activation == "silu"
                      else jax.nn.gelu)
    return False, _activation(cfg.activation)


def moe_mlp(p: Dict[str, Any], x: jnp.ndarray, cfg,
            rng: Optional[jax.Array] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE block (reference ``MOELayer.forward``, ``moe/sharded_moe.py:425``).

    x: [B, S, D] → (out [B, S, D], aux_loss scalar).
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    capacity = int(np.ceil(t * cfg.capacity_factor * k / e))
    capacity = max(capacity, k)

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    dispatch, combine, aux = topk_gating(logits, k, capacity, rng,
                                         cfg.router_jitter,
                                         cfg.norm_topk_prob)

    # dispatch → [E, C, D]; sharded over the expert axis so the einsum below is
    # the all-to-all the reference implements by hand (_AllToAll, sharded_moe.py:95)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    expert_in = constrain(expert_in, "expert", None, None)

    glu, act = _expert_form(cfg)

    def one_expert(w, h):  # h: [C, D]
        # the dense MLP's names for a checkpointed layer (models/remat.py)
        up = checkpoint_name(jnp.einsum("cd,df->cf", h, w["w_up"]), "mlp_up")
        mid = act(checkpoint_name(jnp.einsum("cd,df->cf", h, w["w_gate"]),
                                  "mlp_gate")) * up if glu else act(up)
        return jnp.einsum("cf,fd->cd", mid, w["w_down"])

    expert_out = jax.vmap(one_expert)(
        {n: p[n] for n in ("w_gate", "w_up", "w_down") if n in p},
        expert_in)                                               # [E, C, D]
    expert_out = constrain(expert_out, "expert", None, None)

    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return out.reshape(b, s, d), aux.astype(jnp.float32)


def _layer_groups(w: jnp.ndarray, group_sizes: jnp.ndarray, layer, dtype
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What ``ragged_dot`` takes of an expert leaf ``w``: the whole stack
    ``[L, E, a, b]`` with the ``layer`` to read, or one layer's ``[E, a, b]``
    (told apart by RANK: a stack of one, read at 0). Returns the ``[L x E, a,
    b]`` view (a reshape of leading axes: a bitcast, nothing moves) and its
    ``[L x E]`` sizes: this layer's E ``group_sizes`` at ``layer x E`` among
    zeros, so the sorted rows fall on this layer's experts where they lie
    and no other layer's are read. Only one layer's leaf may be of another
    ``dtype``: casting a stack would convert L x the bytes every layer.

    The path of every platform but the TPU (:func:`moe_mlp_nodrop`), and
    the zero groups are its alone: the TPU's kernel takes ``layer`` as a
    scalar-prefetch operand of the weights' ``BlockSpec`` and never sees
    another layer's experts (``ops/grouped_gemm.py``)."""
    if w.ndim == 3:
        w, layer = w.astype(dtype)[None], 0
    elif w.dtype != dtype:
        raise ValueError(
            f"a stack of expert weights must have the activations' dtype "
            f"({w.dtype} != {dtype}): hand one layer's slice instead")
    n_layers, e = w.shape[:2]
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * e,), group_sizes.dtype), group_sizes,
        (layer * e,))
    return w.reshape((n_layers * e,) + w.shape[2:]), sizes


def combine_rows(ys: jnp.ndarray, at: jnp.ndarray, gate_w: jnp.ndarray,
                 has_expert: Optional[jnp.ndarray], dtype) -> jnp.ndarray:
    """The experts' combine (the reference's ``moe_gather``): ``out[t] =
    sum_j gate_w[t, j] * ys[at[t * k + j]]`` as ONE gather of a token's ``k``
    rows and a weighted sum over them in float32, rounded once to ``dtype``.
    ``ys`` [rows, D] the down projection's result wherever its rows lie;
    ``at`` [T*k] the row of each (token, choice), token-major; ``gate_w``
    [T, k] float32; ``has_expert`` [T*k] bool or None (every row has one).
    A row with no expert here is SELECTED away, not multiplied: what lies at
    its ``at`` is whatever the grouped matmul left there (NaN is allowed),
    and it adds an exact zero."""
    t, k = gate_w.shape
    # choice-major, [k, T, D], and the sum written out over the k slices:
    # one fusion reads the gathered rows once. Token-major, a token's k rows
    # are padded to a tile of 8 and the float32 [T, k, D] is written out
    # (120 MiB at DeepSeek-V2's 768 x 6 x 5,120); so is [k x T, D] in float32
    # where all of ``rows`` is cast before it is sliced (compiled for the
    # v5e, PR 58)
    rows = ys[at.reshape(t, k).T]
    w = gate_w.T[:, :, None]
    has = None if has_expert is None \
        else has_expert.reshape(t, k).T[:, :, None]
    out = 0.0
    for j in range(k):
        term = rows[j].astype(jnp.float32) * w[j]
        out = out + (term if has is None else jnp.where(has[j], term, 0.0))
    # the sum ends HERE: left to itself the compiler fuses it into the
    # layer's last residual add, and the gathered [k x T, D] rows (48 MiB at
    # Command A+'s 8 x 768 x 4,096) stay alive across whatever stands between
    # (a parallel block's whole attention: PERF.md section 6, PR 58)
    return jax.lax.optimization_barrier(out.astype(dtype))


def moe_mlp_nodrop(p: Dict[str, Any], x: jnp.ndarray, cfg,
                   live: Optional[jnp.ndarray] = None, layer=0
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k MoE for flat token streams (the serving path).

    The reference serves MoE through ``moe_scatter`` → CUTLASS grouped GEMM →
    ``moe_gather`` (``inference/v2/kernels/ragged_ops/``,
    ``modules/implementations/moe/cutlass_multi_gemm.py``). TPU-native
    equivalent: sort (token, choice) rows by expert and run the three expert
    GEMMs as grouped matmuls. No capacity truncation — inference must never
    drop a routed token (unlike the training path's capacity buffers,
    :func:`moe_mlp`).

    The PLATFORM picks the grouped matmul, nothing else does
    (``ops.grouped_gemm.default_impl``). On the TPU: the Pallas kernel of
    ``ops/grouped_gemm.py``, whose row tile fits the rows an expert gets
    (``row_tile``, by the static shape); the sorted rows are laid out with
    every expert's first on a tile boundary (``tile_rows``), gate and up
    share one read of them with the activation on the float32 accumulators,
    and the result stays in that layout for the combine. Anywhere else (and
    as the tests' reference): ``jax.lax.ragged_dot`` over
    :func:`_layer_groups`, three calls with the activation between. Routing,
    the sort and the combine are the same code on both.

    The combine (``moe_gather``) scatters nothing: every token has exactly
    ``k`` rows at places the sort knows, so the sort is inverted (one more
    small sort) and :func:`combine_rows` GATHERS each token's ``k`` rows,
    out of the tile layout or the sorted rows, and sums them under the gate
    weights in float32 with one rounding to ``x.dtype``. (A scatter-add of
    ``T x k`` rows runs a row at a time on the TPU, ~26 GB/s: PERF.md
    section 6, PRs 36 and 58.)

    ``live`` [T] bool: the serving forwards always carry their full row
    budget, pads included. A row that is not live gets NO expert: it sorts
    behind the last group, outside ``group_sizes``, and its output is zero.

    The router is ``cfg.num_experts`` wide; the expert leaves hold
    ``cfg.experts_held`` of them, ids ``cfg.first_expert_held`` onward (all
    of them unless the program is one chip's share of an expert-parallel
    layer). A (token, choice) row routed to an expert that is not here gets
    no expert, exactly as a dead row: what the absent experts would have
    added is left out, and the partial sum is the layer's result. On one
    chip there is no exchange, and nothing stands in for the other chips.

    A model with shared experts (``p["shared"]``) adds their MLP of every
    row beside the routed sum: ONE MLP as wide as all of them, which is
    their sum; ``cfg.shared_expert_combine == "average"`` adds their MEAN,
    that MLP's output over ``cfg.n_shared_experts``.

    ``cfg.mlp_type == "mlp"``: an expert is TWO matrices and no gate,
    ``act(x w_up) w_down`` (nemotron_h's ``relu^2`` experts), the activation
    on the up projection's float32 accumulator (``grouped_act``); the shared
    expert is then ``layers.std_mlp``'s ``fc1`` / ``fc2``.

    ``p["w_gate" | "w_up" | "w_down"]`` are one layer's ``[E_held, ., .]`` or
    the whole stack ``[L, E_held, ., .]`` with ``layer`` (static or traced)
    the layer to read: a custom call (the TPU's kernel; ``ragged_dot`` where
    the compiler makes it one) takes whole buffers, so a slice ``w[layer]``
    handed to it is first COPIED out of the stack (three matrices a layer,
    1.56 x the GEMMs' own time in OLMoE's decode step). The serving layer
    loop therefore hands the stack whole
    (``inference/v2/model.py:_scan_layers``); the kernel reads ``layer`` as a
    scalar-prefetch operand, and on the other platforms
    :func:`_layer_groups` places the rows on the layer's experts among
    ``L x E`` groups, all but E of them empty. Every other leaf of ``p`` is
    the layer's own.

    x: [T, D] flat tokens → (out [T, D], routed [E] int32: the (token,
    choice) rows the router gave each of ITS experts, here or not, ``sum ==
    k × live rows``).
    """
    from ..monitor.mfu import scope
    from ..ops import grouped_gemm

    t = x.shape[0]
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    held, first = cfg.experts_held, cfg.first_expert_held
    impl = grouped_gemm.default_impl()   # by platform: the kernel on the TPU
    with scope("moe_route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        bias = p.get("router_bias")
        gate_w, expert_idx = topk_weights(
            router_scores(logits, cfg), k, cfg.norm_topk_prob,
            None if bias is None else bias.astype(jnp.float32),
            cfg.routed_scaling_factor,
            (cfg.n_group, cfg.topk_group)
            if cfg.topk_method == "group_limited_greedy" else None)

        flat_expert = expert_idx.reshape(t * k)
        has_expert = None      # [T*k] bool: the rows that get an expert
        if live is not None:
            # expert id E = "none": sorts last and is in no group
            has_expert = jnp.repeat(live, k)
            flat_expert = jnp.where(has_expert, flat_expert, e)
        here = flat_expert
        if held != e:
            # the id among the experts held; ``held`` = "none here"
            here = flat_expert - first
            has_expert = (here >= 0) & (here < held)
            here = jnp.where(has_expert, here, held)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(here, stable=True)                # moe_scatter
        sorted_tok = flat_tok[order]
        routed = jnp.bincount(flat_expert, length=e).astype(jnp.int32)
        group_sizes = routed if held == e else routed[cfg.held_experts]
        if impl != "xla":
            # the kernel's rows: every expert's start on a tile boundary
            tiles = grouped_gemm.tile_rows(
                group_sizes, here[order], grouped_gemm.row_tile(t * k, e))
            xs = x[sorted_tok[tiles.src]]                     # [tiles, D]
        else:
            xs = x[sorted_tok]                                # [T*k, D]

    glu, act = _expert_form(cfg)
    with scope("moe_experts"):
        if impl == "xla":
            def grouped(rows, w):
                return jax.lax.ragged_dot(
                    rows, *_layer_groups(w, group_sizes, layer, x.dtype))

            up = grouped(xs, p["w_up"])
            mid = act(grouped(xs, p["w_gate"])) * up if glu else act(up)
            ys = grouped(mid, p["w_down"])                    # [T*k, D]
        else:
            kw = dict(layer=layer, interpret=impl == "pallas_interpret")
            mid = grouped_gemm.grouped_glu(
                xs, p["w_gate"], p["w_up"], tiles, act=act, **kw) if glu \
                else grouped_gemm.grouped_act(xs, p["w_up"], tiles, act=act,
                                              **kw)
            ys = grouped_gemm.grouped_matmul(mid, p["w_down"], tiles,
                                             **kw)       # [tiles, D]

    with scope("moe_combine"):
        # moe_gather: where each (token, choice) row went in the sort, and
        # from there in the kernel's tiles; the down projection's result is
        # read ONCE, where it lies, and nothing is scattered
        inv = jnp.argsort(order)
        out = combine_rows(ys, inv if impl == "xla" else tiles.dest[inv],
                           gate_w, has_expert, x.dtype)
    if "shared" in p:
        with scope("moe_shared"):
            shared = (glu_mlp if glu else std_mlp)(p["shared"], x[None],
                                                   cfg)[0]
            if cfg.shared_expert_combine == "average":
                shared = shared * (1.0 / cfg.n_shared_experts)
            out = out + shared
    return out, routed
