"""Transformer building blocks, written TPU-first.

Functional (params-in, activations-out) equivalents of the reference's fused modules
(``deepspeed/ops/transformer/inference/ds_attention.py``, ``ds_mlp.py``,
``csrc/transformer/*``): on TPU the elementwise/norm fusion those CUDA kernels provide
comes from XLA, so these are plain jnp compositions; the genuinely kernel-worthy op
(attention over long sequences) dispatches through :func:`attention` to a Pallas flash
kernel when on TPU (``ops/flash_attention.py``) and to an exact jnp reference elsewhere.

Sharding: activations are annotated with logical axes via :func:`constrain` so the
SPMD partitioner keeps batch over (data, fsdp), sequence over seq, and heads/ffn over
model — the activation-layout contract TP/SP rest on.
"""
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .config import ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------- sharding
def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """Best-effort ``with_sharding_constraint`` against the world topology.

    No-op when no topology is installed (pure single-device use) or when the spec
    doesn't apply (axis missing from the mesh). Model code stays mesh-agnostic.
    """
    from ..comm import topology as topo_mod

    topo = topo_mod._WORLD_TOPOLOGY
    if topo is None:
        return x
    # inside a shard_map manual region (ZeRO++ explicit step, pipeline ring)
    # a constraint naming manual axes is rejected at lowering — and the data
    # is already placed per-shard there, so the constraint is meaningless.
    manual = _manual_axes()
    if manual:
        used = {a for s in spec
                for a in (s if isinstance(s, (tuple, list)) else (s,)) if a}
        if used & manual:
            return x
    try:
        return jax.lax.with_sharding_constraint(x, topo.sharding(*spec))
    except (ValueError, TypeError):
        return x


def _manual_axes() -> set:
    """Mesh axes the enclosing ``shard_map`` (if any) made manual."""
    return set(jax.sharding.get_abstract_mesh().manual_axes)


BATCH = ("data", "fsdp")  # input batch dim is split over both DP-ish axes


# --------------------------------------------------------------------------- norm
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm (reference kernel: ``csrc/transformer/inference/csrc/rms_norm.cu``;
    XLA fuses the reduction+rescale chain on TPU)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray,
               bias: Optional[jnp.ndarray], eps: float) -> jnp.ndarray:
    """LayerNorm with learned bias (reference ``csrc/transformer/inference/csrc/
    layer_norm.cu``) — the GPT-2/OPT/BLOOM/Falcon-era norm; ``bias`` None:
    scale only (cohere)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def norm(x: jnp.ndarray, p: Params, cfg: ModelConfig) -> jnp.ndarray:
    """Norm dispatch on ``cfg.norm_type`` over a ``{"scale"[, "bias"]}`` leaf dict."""
    if cfg.norm_type == "layernorm":
        return layer_norm(x, p["scale"], p.get("bias"), cfg.rms_norm_eps)
    return rms_norm(x, p["scale"], cfg.rms_norm_eps)


def qk_norm(p: Params, q: jnp.ndarray, k: jnp.ndarray,
            cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """OLMoE's QK-norm (``cfg.qk_norm``): RMSNorm over the WHOLE q and k
    projections, ``[..., q_dim]`` and ``[..., kv_dim]`` with one learned
    scale each, BEFORE the split into heads and before rotary (HF
    ``modeling_olmoe``: ``q_norm(q_proj(x))``). Shared by the training block
    and the serving block's ``_qkv``."""
    if cfg.qk_head_norm:
        # one RMSNorm a HEAD, over its head_dim, one [head_dim] scale for
        # q's heads and one for k's (``cfg.qk_head_norm``)
        def per_head(t, scale):
            heads = t.reshape(*t.shape[:-1], -1, cfg.head_dim)
            return rms_norm(heads, scale, cfg.rms_norm_eps).reshape(t.shape)

        return (per_head(q, p["q_norm"]["scale"]),
                per_head(k, p["k_norm"]["scale"]))
    if not cfg.qk_norm:
        return q, k
    return (rms_norm(q, p["q_norm"]["scale"], cfg.rms_norm_eps),
            rms_norm(k, p["k_norm"]["scale"], cfg.rms_norm_eps))


# --------------------------------------------------------------------------- rope
def rope_frequencies(head_dim: int, theta: float,
                     scaling: Optional[Dict[str, Any]] = None) -> np.ndarray:
    """Rotary frequencies [head_dim / 2]. ``scaling`` (``cfg.rope_scaling``,
    type ``yarn``; Peng et al., arXiv:2309.00071, as DeepSeek-V2 computes
    it): each frequency is a blend of itself and itself / ``factor`` by a
    linear ramp between the two correction dimensions, the pair indices
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context: fast pairs keep their frequency, slow ones are interpolated."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                             / head_dim))
    if not scaling:
        return freqs
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return head_dim * np.log(orig / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(int(np.floor(correction_dim(scaling["beta_fast"]))), 0)
    high = min(int(np.ceil(correction_dim(scaling["beta_slow"]))),
               head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (freqs / scaling["factor"] * ramp
            + freqs * (1.0 - ramp)).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               rotary_dim: Optional[int] = None,
               scaling: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
    """Rotary embedding (reference kernel: ``csrc/transformer/inference/csrc/
    apply_rotary_pos_emb.cu``). x: [B, S, H, D]; positions: [B, S] or [S].
    ``rotary_dim < D`` rotates only the leading dims (GPT-NeoX/GPT-J/Phi
    partial rotary; ingestion converts interleaved layouts to this split-half
    convention by permuting q/k weight columns). ``scaling``:
    :func:`rope_frequencies`' YaRN blend."""
    head_dim = x.shape[-1]
    rd = head_dim if rotary_dim is None else rotary_dim
    x_rot, x_pass = (x, None) if rd == head_dim else (x[..., :rd], x[..., rd:])
    freqs = jnp.asarray(rope_frequencies(rd, theta, scaling))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, rd/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, rd/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    return out if x_pass is None else jnp.concatenate([out, x_pass], axis=-1)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (reference builds these in
    ``module_inject/containers/bloom.py``-served models via HF; standard
    geometric schedule from the ALiBi paper, non-power-of-2 interpolation)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    n = 2 ** int(np.floor(np.log2(num_heads)))
    slopes = pow2_slopes(n)
    if n < num_heads:
        extra = pow2_slopes(2 * n)[0::2][: num_heads - n]
        slopes = np.concatenate([slopes, extra])
    return slopes.astype(np.float32)


# --------------------------------------------------------------------------- attention
def reference_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True,
                        segment_ids: Optional[jnp.ndarray] = None,
                        kv_positions_below: Optional[jnp.ndarray] = None,
                        kv_mask: Optional[jnp.ndarray] = None,
                        alibi: Optional[jnp.ndarray] = None,
                        window: Optional[int] = None,
                        q_positions: Optional[jnp.ndarray] = None,
                        kv_positions: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """Exact softmax attention in jnp — the parity reference for the Pallas kernels
    (the role torch plays for the reference's kernel tests, SURVEY.md §4).

    q: [B, Sq, H, D], k/v: [B, Skv, KVH, D]. GQA handled by head repetition.
    ``kv_positions_below``: decode-mode masking — attend only to kv slots < this
    per-query position (used with a prefilled KV cache where Sq << Skv).
    ``kv_mask``: [B, Skv] explicit slot-validity mask, ANDed in — needed when
    cache slot index ≠ token position (right-padded ragged batches, where pad
    slots sit between each prompt's end and the shared decode region).
    ``alibi``: per-head slopes [H] — adds ``slope·(k_pos − q_pos)`` to logits
    (BLOOM-family positional scheme). ``window``: sliding-window local
    attention — queries see only the last ``window`` positions (Mistral).
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    skv = k.shape[1]
    explicit_pos = q_positions is not None and kv_positions is not None
    if explicit_pos:
        # true logical positions (ragged decode: slot index ≠ position)
        q_pos = q_positions.astype(jnp.int32)[:, None, :, None]
        k_pos = kv_positions.astype(jnp.int32)[:, None, None, :]
    elif kv_positions_below is not None:
        q_pos = (kv_positions_below - 1).astype(jnp.int32)[:, None, :, None]
        k_pos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32)[None, None,
                                                                  None, :],
                                 (b, 1, sq, skv))
    else:
        q_pos = (jnp.arange(sq, dtype=jnp.int32)
                 + (skv - sq))[None, None, :, None]
        k_pos = jnp.arange(skv, dtype=jnp.int32)[None, None, None, :]
    if alibi is not None:
        logits = logits + alibi.astype(jnp.float32)[None, :, None, None] * (
            k_pos - q_pos).astype(jnp.float32)
    mask = None
    if explicit_pos:
        if causal:
            mask = k_pos <= q_pos  # position-space causality
    elif kv_positions_below is not None:
        kv_idx = jnp.arange(skv)[None, None, :]
        mask = kv_idx < kv_positions_below[:, :, None]  # [B, Sq, Skv]
        mask = mask[:, None, :, :]
    elif causal:
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(skv)[None, :]
        mask = (ki <= qi + (skv - sq))[None, None, :, :]
    if window is not None:
        wmask = (q_pos - k_pos) < window
        mask = wmask if mask is None else jnp.logical_and(mask, wmask)
    if segment_ids is not None:
        seg = (segment_ids[:, None, :, None] == segment_ids[:, None, None, :]) \
            if segment_ids.shape[1] == sq and sq == skv else None
        if seg is not None:
            mask = seg if mask is None else jnp.logical_and(mask, seg)
    if kv_mask is not None:
        m = kv_mask[:, None, None, :]  # [B, 1, 1, Skv]
        mask = m if mask is None else jnp.logical_and(mask, m)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _cached_flash_attention(q, k, v, causal, kv_positions_below, kv_mask,
                            alibi=None, window=None, q_positions=None,
                            kv_positions=None, interpret=None):
    """KV-cache attention through the flash kernel (the v1 engine's prefill
    and decode steps). Slot-space masks map onto the kernel's ragged mode:
    ``kv_positions_below`` becomes explicit q positions (query i sees slots
    < below[i] ⇔ slot index <= below[i]-1; kv positions default to slot
    indices), and ``kv_mask`` becomes a kv segment id (-1 = invalid slot,
    matching no query). ``segment_ids`` are deliberately NOT consumed here,
    matching :func:`reference_attention`, which ignores them whenever
    Sq != Skv (the cached case)."""
    from ..ops.flash_attention import flash_attention

    b, sq = q.shape[:2]
    skv = k.shape[1]
    use_causal = causal
    if q_positions is not None and kv_positions is not None:
        # true logical positions (ragged: slot ≠ position) — position-space
        # causality, and alibi/window distances come out right
        q_pos, kv_pos = (q_positions.astype(jnp.int32),
                         kv_positions.astype(jnp.int32))
        use_causal = True
    elif kv_positions_below is not None:
        q_pos = kv_positions_below.astype(jnp.int32) - 1     # [B, Sq]
        kv_pos = None
        use_causal = True
    else:
        q_pos = kv_pos = None
    seg_q = seg_k = None
    if kv_mask is not None:
        seg_q = jnp.zeros((b, sq), jnp.int32)
        seg_k = jnp.where(kv_mask, 0, -1).astype(jnp.int32)
    return flash_attention(q, k, v, causal=use_causal,
                           segment_ids=seg_q, kv_segment_ids=seg_k,
                           q_positions=q_pos, kv_positions=kv_pos,
                           alibi=alibi, window=window,
                           interpret=interpret)


def _flash_over_mesh(q, k, v, *, causal, segment_ids, alibi, window):
    """The flash kernel under the world mesh. XLA cannot partition a Mosaic
    kernel ("wrap the call in a shard_map" — raised at lowering on a
    multi-chip TPU, where it used to be caught and turned into the XLA
    reference), so on a multi-device topology the call runs per shard:
    batch over (data, fsdp), heads over model — attention is independent
    across both, so no collective is needed. Inside an enclosing manual
    region (ZeRO++ explicit step, pipeline ring) the data is per-shard
    already and the kernel is called as is."""
    from jax.sharding import PartitionSpec as P

    from ..comm import topology as topo_mod
    from ..ops.flash_attention import flash_attention

    call = partial(flash_attention, causal=causal, window=window)
    topo = topo_mod._WORLD_TOPOLOGY
    if topo is None or topo.world_size() == 1 or _manual_axes():
        return call(q, k, v, segment_ids=segment_ids, alibi=alibi)
    sizes = topo.axis_sizes
    if sizes["seq"] > 1:
        raise NotImplementedError(
            "attn_impl='flash' on a sequence-parallel mesh: use "
            "'ring:flash' or 'ulysses:flash'")
    batch = tuple(a for a in BATCH if sizes[a] > 1) or None
    heads = "model" if sizes["model"] > 1 else None
    n_batch = int(np.prod([sizes[a] for a in batch or ()]))
    if q.shape[0] % n_batch or q.shape[2] % sizes["model"] \
            or k.shape[2] % sizes["model"]:
        raise ValueError(
            f"flash attention over mesh {sizes}: batch {q.shape[0]} must "
            f"divide by {n_batch} and heads {q.shape[2]}/{k.shape[2]} by "
            f"{sizes['model']}")
    qkv = P(batch, None, heads, None)
    extra = [(name, val, spec) for name, val, spec in (
        ("segment_ids", segment_ids, P(batch, None)),
        ("alibi", alibi, P(heads))) if val is not None]

    def per_shard(q, k, v, *vals):
        return call(q, k, v, **{n: x for (n, _, _), x in zip(extra, vals)})

    return jax.shard_map(
        per_shard, mesh=topo.mesh,
        in_specs=(qkv, qkv, qkv) + tuple(spec for _, _, spec in extra),
        out_specs=qkv, check_vma=False)(
        q, k, v, *(jnp.asarray(val) for _, val, _ in extra))


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              impl: str = "auto",
              causal: bool = True,
              segment_ids: Optional[jnp.ndarray] = None,
              kv_positions_below: Optional[jnp.ndarray] = None,
              kv_mask: Optional[jnp.ndarray] = None,
              alibi: Optional[jnp.ndarray] = None,
              window: Optional[int] = None,
              q_positions: Optional[jnp.ndarray] = None,
              kv_positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Attention dispatch — the seam where Pallas/SP implementations plug in
    (reference analog: the op-binding indirection of
    ``ops/transformer/inference/op_binding/``).

    Sequence-parallel impls take an inner (per-shard) implementation after
    a colon — ``"ring:flash"`` / ``"ring:xla"`` / ``"ulysses:flash"`` /
    ``"ulysses:xla"``; bare ``"ring"``/``"ulysses"`` auto-select (flash on
    TPU).
    """
    inner = None
    if impl and ":" in impl:
        impl, inner = impl.split(":", 1)
        if impl not in ("ring", "ulysses"):
            raise ValueError(
                f"attn_impl {impl + ':' + inner!r}: only the "
                f"sequence-parallel impls take an inner "
                f"('ring:...'/'ulysses:...')")
        if inner not in ("flash", "xla"):
            # a typo'd inner silently falling back would make an A/B
            # compare an arm against itself and report a bogus no-diff
            raise ValueError(f"unknown inner attention impl {inner!r} "
                             f"(flash | xla)")
    if (window is not None and not causal
            and kv_positions_below is None and kv_positions is None):
        # the window bound is one-sided (how far BACK a query sees) on every
        # backend; with no other causality mechanism in play (cached decode
        # supplies kv_positions_below/kv_positions instead of the flag),
        # rejecting here keeps flash and xla behavior identical instead of
        # raising on one platform and silently attending to unbounded
        # future keys on the other
        raise ValueError("window requires causal=True (the sliding window "
                         "only bounds attention to the past)")
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if (kv_positions_below is not None or kv_mask is not None
            or kv_positions is not None):
        # cached-decode masking (slot validity + slot- or position-space
        # causality). The flash kernel handles it via explicit position
        # arrays + kv segment ids; ring/ulysses are training patterns and
        # fall back to xla.
        if impl == "flash":
            return _cached_flash_attention(q, k, v, causal,
                                           kv_positions_below, kv_mask,
                                           alibi=alibi, window=window,
                                           q_positions=q_positions,
                                           kv_positions=kv_positions)
        impl = "xla"
    if impl == "flash":
        return _flash_over_mesh(q, k, v, causal=causal,
                                segment_ids=segment_ids, alibi=alibi,
                                window=window)
    if impl in ("ring", "ulysses") and (alibi is not None
                                        or window is not None):
        # silently materializing O(S²) logits would defeat the point of SP
        raise NotImplementedError(
            f"attn_impl={impl!r} does not support alibi/sliding-window yet; "
            f"use attn_impl='flash' or 'xla'")
    if impl == "ring":
        from ..parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=causal, inner=inner)
    if impl == "ulysses":
        from ..parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=causal,
                                 segment_ids=segment_ids, inner=inner)
    if impl != "xla":
        raise ValueError(f"unknown attn_impl {impl!r} "
                         f"(auto | xla | flash | ring | ulysses)")
    return reference_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                               kv_positions_below=kv_positions_below,
                               kv_mask=kv_mask, alibi=alibi, window=window,
                               q_positions=q_positions,
                               kv_positions=kv_positions)


# --------------------------------------------------------------------------- blocks
def _kv_memory_shardings():
    """(host, device) shardings for a per-layer cache slice [B, len, KVH,
    hd] under the world topology — TP keeps kv heads on the model axis in
    BOTH memory spaces."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..comm.topology import get_world_topology

    topo = get_world_topology()
    spec = P(None, None, "model", None)
    return (NamedSharding(topo.mesh, spec, memory_kind="pinned_host"),
            NamedSharding(topo.mesh, spec, memory_kind="device"))


_WINDOW_FROM_CFG = object()  # sentinel: "use cfg.sliding_window"


def attention_block(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                    positions: jnp.ndarray,
                    segment_ids: Optional[jnp.ndarray] = None,
                    kv_cache: Optional[Tuple] = None,
                    impl: Optional[str] = None,
                    kv_mask: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    window_override=_WINDOW_FROM_CFG):
    """Self-attention sublayer: qkv proj → RoPE → attention → out proj.

    With ``kv_cache=(k_cache, v_cache, write_pos)`` runs in decode mode: appends
    current k/v at ``write_pos`` and attends over the cache (the role of the
    reference's ``linear_blocked_kv_rotary`` + ``blocked_flash`` kernels,
    ``inference/v2/kernels/ragged_ops/``). Returns (out, new_kv_cache).

    The whole sublayer traces under the ``attn`` MFU region scope
    (``monitor/mfu.py``): XLA stamps the label into every lowered op's
    metadata (backward included — the transpose wrapper preserves it), so
    the step-time attribution ledger can name attention's share of a
    measured step.
    """
    from ..monitor.mfu import region_scope

    with region_scope("attn"):
        return _attention_block_impl(p, x, cfg, positions, segment_ids,
                                     kv_cache, impl, kv_mask, kv_positions,
                                     window_override)


def _attention_block_impl(p, x, cfg, positions, segment_ids, kv_cache, impl,
                          kv_mask, kv_positions, window_override):
    b, s, d = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    # names for a checkpointed layer's policy (models/remat.py); inert
    # without one. From here to the kernel everything is elementwise
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_k")
    v = checkpoint_name(v, "attn_v")
    q, k = qk_norm(p, q, k, cfg)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    q = constrain(q, BATCH, "seq", "model", None)
    k = constrain(k, BATCH, "seq", "model", None)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
    alibi = (jnp.asarray(alibi_slopes(cfg.num_heads) * cfg.alibi_scale)
             if cfg.pos_embed == "alibi" else None)
    window = (cfg.sliding_window if window_override is _WINDOW_FROM_CFG
              else window_override)
    if cfg.attn_scale is not None:
        # non-standard logit scale (GPT-Neo uses 1.0, not 1/√d): fold the
        # correction into q so every attention backend (flash kernel, xla
        # oracle, ring/ulysses) inherits it without a kernel knob
        q = q * jnp.asarray(cfg.attn_scale * np.sqrt(cfg.head_dim),
                            q.dtype)

    new_cache = None
    if kv_cache is not None:
        k_cache, v_cache, write_pos = kv_cache
        # ZeRO-Inference KV offload: a host-resident cache (detected from
        # the traced memory space) is updated IN host space — the new
        # token's k/v hop to host, the single-token write stays there —
        # and the full per-layer slice streams to device for attention.
        # HBM holds one layer's cache at a time instead of all of them.
        cache_space = getattr(k_cache.aval, "memory_space", None)
        offloaded = (cache_space is not None
                     and cache_space != getattr(k.aval, "memory_space",
                                                cache_space))
        if offloaded:
            host_s, dev_s = _kv_memory_shardings()
            k = jax.device_put(k, host_s)
            v = jax.device_put(v, host_s)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, write_pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, write_pos, axis=1)
        new_cache = (k_cache, v_cache, write_pos + s)
        if offloaded:
            k_cache = jax.device_put(k_cache, dev_s)
            v_cache = jax.device_put(v_cache, dev_s)
        if kv_positions is not None:
            # ragged with true per-slot positions supplied (engine knows
            # slot→position): position-space causality, and alibi/window
            # distances are computed on logical positions, not cache slots
            out = attention(q, k_cache, v_cache, impl=impl or cfg.attn_impl,
                            causal=True, kv_mask=kv_mask, alibi=alibi,
                            window=window, q_positions=positions,
                            kv_positions=kv_positions)
        else:
            if kv_mask is not None:
                # ragged right-padded batches without per-slot positions:
                # causality must be slot-space — query i of this chunk
                # (written at write_pos+i) sees slots <= write_pos+i;
                # kv_mask supplies validity of the rest
                kv_below = write_pos + jnp.arange(s)[None, :] + 1
                if cfg.pos_embed == "alibi" or window is not None:
                    # the EFFECTIVE window (cfg.sliding_window or the
                    # per-layer override) — slot-space distances would be
                    # silently wrong either way
                    raise ValueError(
                        "alibi/sliding-window ragged decode needs kv_positions"
                        " (slot index ≠ logical position would skew distances)")
            else:
                kv_below = positions + 1  # slot == position: own pos or before
            out = attention(q, k_cache, v_cache, impl=impl or cfg.attn_impl,
                            causal=False, kv_positions_below=kv_below,
                            kv_mask=kv_mask, alibi=alibi, window=window)
    else:
        out = attention(q, k, v, impl=impl or cfg.attn_impl, causal=True,
                        segment_ids=segment_ids, alibi=alibi, window=window)
    out = out.reshape(b, s, cfg.q_dim)
    out = jnp.einsum("bsq,qd->bsd", out, p["wo"])
    if cfg.attn_out_bias:
        out = out + p["bo"].astype(out.dtype)
    # the MLP's weight gradients read the stream AFTER this sublayer
    out = checkpoint_name(out, "attn_out")
    return constrain(out, BATCH, "seq", None), new_cache


def _activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_exact": partial(jax.nn.gelu, approximate=False),
            "relu": jax.nn.relu,
            # squared ReLU (Primer; nemotron_h's mlp_hidden_act)
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


def scaled(x: jnp.ndarray, by: float) -> jnp.ndarray:
    """``x * by`` for one of muP's multipliers (``ModelConfig.mup``; 1.0:
    ``x`` as it is), the product in float32: a multiplier rounded to bf16
    first would be off by up to 0.4 % in every element alike."""
    return x if by == 1.0 else (x.astype(jnp.float32) * by).astype(x.dtype)


def glu_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Gated-linear-unit MLP (SwiGLU/GeGLU). Reference fuses bias+activation in
    ``csrc/transformer/inference/csrc/gelu.cu`` / v2 ``gated_activations``; XLA
    fuses the same chain into the matmul epilogue on TPU. Under muP
    (``cfg.mup.mlp``) the gate is scaled before its activation and the
    down-projection's output after it."""
    act = _activation(cfg.activation)
    gate = checkpoint_name(jnp.einsum("bsd,df->bsf", x, p["w_gate"]),
                           "mlp_gate")
    up = checkpoint_name(jnp.einsum("bsd,df->bsf", x, p["w_up"]), "mlp_up")
    h = act(scaled(gate, cfg.mup.mlp[0])) * up
    h = constrain(h, BATCH, "seq", "model")
    return scaled(jnp.einsum("bsf,fd->bsd", h, p["w_down"]), cfg.mup.mlp[1])


def std_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Two-matrix MLP (fc1 → act → fc2), the GPT-2/OPT/BLOOM/Falcon/Phi shape
    (reference fused path: ``csrc/transformer/inference/csrc/gelu.cu``
    fused_bias_gelu)."""
    act = _activation(cfg.activation)
    h = jnp.einsum("bsd,df->bsf", x, p["fc1"])
    if cfg.use_bias:
        h = h + p["b1"].astype(h.dtype)
    h = act(checkpoint_name(h, "mlp_up"))
    h = constrain(h, BATCH, "seq", "model")
    out = jnp.einsum("bsf,fd->bsd", h, p["fc2"])
    if cfg.use_bias:
        out = out + p["b2"].astype(out.dtype)
    return out


def mlp_block(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    from ..monitor.mfu import region_scope

    with region_scope("mlp"):  # MFU-region label (see attention_block)
        return (std_mlp(p, x, cfg) if cfg.mlp_type == "mlp"
                else glu_mlp(p, x, cfg))
