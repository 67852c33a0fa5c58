"""Pallas flash attention — fused streaming-softmax attention, fwd + bwd.

The training/prefill attention kernel: the TPU-native answer to the
reference's fused-attention native code (v1 inference fused softmax/attention
``csrc/transformer/inference/csrc/``, the CUTLASS EvoformerAttention family
``csrc/deepspeed4science/evoformer_attn/`` ~14.9k LoC, and v2's
``blocked_flash``). One kernel family, three Pallas kernels total:

* forward: grid (batch, q_head, q_block, kv_block) with the kv dimension
  innermost-sequential; online-softmax state (m, l, acc) lives in VMEM
  scratch that persists across the kv sweep, so logits are never
  materialized in HBM — O(S) memory vs the O(S²) jnp reference.
* backward: the standard two-kernel split — dQ accumulates over kv blocks,
  dK/dV accumulate over q blocks — recomputing probabilities from the saved
  per-row logsumexp (flash-attention-2 style), wired as a ``jax.custom_vjp``.
* GQA: kv blocks are indexed by ``q_head // group`` in the BlockSpec index
  map, so grouped q heads stream the same KV block out of HBM once; the
  backward produces per-q-head dK/dV and group-sums outside the kernel.

Masking supports causal (with Sq != Skv offsets), packed-sequence
``segment_ids``, and length padding (sequences pad to block multiples, the
pad region is masked). Causality compares explicit POSITION arrays, so the
ragged packed-KV prefill path (``inference/v2/model.py``) can run many
variable-context sequences in one call: q tokens carry their position within
their own sequence, the packed KV carries per-slot positions, and separate
q/kv segment ids bound each sequence. Off-TPU the kernels run in interpret
mode, which is also how the parity tests exercise them (SURVEY.md §4
pattern).
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CompilerParams = pltpu.CompilerParams

NEG_INF = -1e30
_LANES = 128

__all__ = ["flash_attention"]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _mask(i, j, seg_q, seg_k, pos_q, pos_k, *, causal, q_len, kv_len,
          block_q, block_k, window=None):
    """[block_q, block_k] validity mask for tile (i, j).

    Causality compares explicit POSITION values (``pos_q``/``pos_k`` blocks)
    rather than array indices — for plain attention the positions are just
    (offset-shifted) iotas, and for the ragged packed-KV prefill path they
    are each token's position within its own sequence. ``window`` adds the
    Mistral-style sliding-window bound (q sees the last ``window`` positions).
    """
    q_idx = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = jnp.logical_and(q_idx < q_len, k_idx < kv_len)
    if causal:
        m = jnp.logical_and(m, pos_k <= pos_q)  # (1,bk) vs (bq,1) broadcast
    if window is not None:
        m = jnp.logical_and(m, pos_q - pos_k < window)
    m = jnp.logical_and(m, seg_q == seg_k)  # (bq,1) vs (1,bk) broadcast
    return m




def _tile_live(seg_q, seg_k, pos_q, pos_k, causal, window=None):
    """Dynamic tile skip: a (q-block, kv-block) tile is dead when no q/kv
    segment pair can match, or (position-causal) when every kv position in
    the block exceeds every q position, or (sliding window) when every kv
    position is below every q position's window. Pallas DMAs the blocks
    regardless, but the three matmuls — the MXU cost — are skipped, which is
    what keeps the packed ragged-prefill path O(tokens x own-context) in
    compute even though the kv stream is the whole packed pool."""
    live = jnp.logical_and(jnp.min(seg_k) <= jnp.max(seg_q),
                           jnp.max(seg_k) >= jnp.min(seg_q))
    if causal:
        live = jnp.logical_and(live, jnp.min(pos_k) <= jnp.max(pos_q))
    if window is not None:
        live = jnp.logical_and(live,
                               jnp.min(pos_q) - jnp.max(pos_k) < window)
    return live


def _bias(s, ab_ref, head, pos_q, pos_k, use_alibi):
    """ALiBi logit bias ``slope·(k_pos − q_pos)`` (zero on the diagonal,
    increasingly negative with distance); the [H,1] slope table sits whole
    in SMEM (Mosaic rejects sub-(8,128) blocked windows even in SMEM) and
    the kernel picks its head's scalar dynamically."""
    if not use_alibi:
        return s
    return s + ab_ref[head, 0] * (pos_k - pos_q).astype(jnp.float32)


def _split_bias_refs(refs, n_fixed, has_bias, has_kbias, has_layout=False):
    """Unpack the optional trailing input refs: ``refs[:n_fixed]`` are the
    always-present inputs; then [pair-bias], [k-row bias], [block layout]."""
    fixed = refs[:n_fixed]
    rest = list(refs[n_fixed:])
    b_ref = rest.pop(0) if has_bias else None
    kb_ref = rest.pop(0) if has_kbias else None
    l_ref = rest.pop(0) if has_layout else None
    assert not rest
    return fixed, b_ref, kb_ref, l_ref


def _layout_live(live, l_ref, i, j):
    """AND a static block-sparsity layout (the reference's SparsityConfig
    layouts, ``ops/sparse_attention/sparsity_config.py``) into the tile-skip:
    layout [Hl, nq, nkv] sits whole in SMEM; dead blocks never touch the
    MXU. Per-head layouts via Hl == H (head program id), Hl == 1 shares one
    layout across heads."""
    if l_ref is None:
        return live
    lh = pl.program_id(1) if l_ref.shape[0] > 1 else 0
    return jnp.logical_and(live, l_ref[lh, i, j] != 0)


def _add_biases(s, b_ref, kb_ref):
    """Additive attention biases (the EvoformerAttention pattern,
    reference ``csrc/deepspeed4science/evoformer_attn/``): a [bq, bk]
    pair-bias tile and/or a [1, bk] per-key row bias, both added AFTER the
    1/√d scaling (the DS4Sci convention)."""
    if b_ref is not None:
        s = s + b_ref[0, 0].astype(jnp.float32)
    if kb_ref is not None:
        s = s + kb_ref[0].astype(jnp.float32)  # [1, bk] broadcasts over rows
    return s


# ------------------------------------------------------------------- forward
def _fwd_kernel(*refs, scale, causal, skip_offset, q_len, kv_len,
                block_q, block_k, num_kv_blocks, use_alibi, window,
                has_bias, has_kbias, has_layout):
    (inputs, b_ref, kb_ref, l_ref) = _split_bias_refs(
        refs[:-5], 8, has_bias, has_kbias, has_layout)
    q_ref, k_ref, v_ref, sq_ref, sk_ref, pq_ref, pk_ref, ab_ref = inputs
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[-5:]
    h = pl.program_id(1)  # hoisted: program_id must not sit inside pl.when
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _bias(s, ab_ref, h, pq_ref[0], pk_ref[0], use_alibi)
        s = _add_biases(s, b_ref, kb_ref)
        mask = _mask(i, j, sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0],
                     causal=causal, q_len=q_len, kv_len=kv_len,
                     block_q=block_q, block_k=block_k, window=window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)                # [bq, LANES]
        alpha = jnp.exp(m_prev - m_next)
        # masked-out entries must stay 0 even when the whole row is masked
        # (NEG_INF - NEG_INF == 0 would otherwise exp to 1)
        p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        pv = jax.lax.dot_general(p, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv

    live = _tile_live(sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0], causal,
                      window)
    live = _layout_live(live, l_ref, i, j)
    if skip_offset is not None:
        # default-position causal: tiles strictly above the shifted diagonal
        # contribute nothing (custom positions rely on the dynamic skip)
        live = jnp.logical_and(
            (i + 1) * block_q - 1 + skip_offset >= j * block_k, live)

    @pl.when(live)
    def _():
        compute()

    @pl.when(j == num_kv_blocks - 1)
    def _():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...][:, :1] + jnp.log(jnp.maximum(l, 1e-30))


# ------------------------------------------------------------------ backward
def _dq_kernel(*refs, scale, causal, skip_offset, q_len, kv_len,
               block_q, block_k, num_kv_blocks, use_alibi, window,
               has_bias, has_kbias, has_layout, emit_dbias):
    n_out = 3 if emit_dbias else 2  # dq_ref [, dbias_ref], dq_scr
    (inputs, b_ref, kb_ref, l_ref) = _split_bias_refs(
        refs[:-n_out], 11, has_bias, has_kbias, has_layout)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, sq_ref, sk_ref,
     pq_ref, pk_ref, ab_ref) = inputs
    if emit_dbias:
        dq_ref, dbias_ref, dq_scr = refs[-3:]
    else:
        (dq_ref, dq_scr), dbias_ref = refs[-2:], None
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _bias(s, ab_ref, h, pq_ref[0], pk_ref[0], use_alibi)
        s = _add_biases(s, b_ref, kb_ref)
        mask = _mask(i, j, sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0],
                     causal=causal, q_len=q_len, kv_len=kv_len,
                     block_q=block_q, block_k=block_k, window=window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)   # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0])                            # [bq, bk]
        if dbias_ref is not None:
            # s = scaled-qk + bias ⇒ ∂L/∂bias tile is exactly ds
            dbias_ref[0, 0] = ds.astype(dbias_ref.dtype)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _tile_live(sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0], causal,
                      window)
    live = _layout_live(live, l_ref, i, j)
    if skip_offset is not None:
        live = jnp.logical_and(
            (i + 1) * block_q - 1 + skip_offset >= j * block_k, live)

    @pl.when(live)
    def _():
        compute()

    if dbias_ref is not None:
        # dead tiles still own their dbias output block — zero it
        @pl.when(jnp.logical_not(live))
        def _():
            dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    @pl.when(j == num_kv_blocks - 1)
    def _():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, skip_offset, q_len, kv_len,
                block_q, block_k, num_q_blocks, use_alibi, window,
                has_bias, has_kbias, has_layout):
    (inputs, b_ref, kb_ref, l_ref) = _split_bias_refs(
        refs[:-4], 11, has_bias, has_kbias, has_layout)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, sq_ref, sk_ref,
     pq_ref, pk_ref, ab_ref) = inputs
    dk_ref, dv_ref, dk_scr, dv_scr = refs[-4:]
    h = pl.program_id(1)
    j = pl.program_id(2)   # kv block (outer)
    i = pl.program_id(3)   # q block (inner, sequential accumulation)

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _bias(s, ab_ref, h, pq_ref[0], pk_ref[0], use_alibi)
        s = _add_biases(s, b_ref, kb_ref)
        mask = _mask(i, j, sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0],
                     causal=causal, q_len=q_len, kv_len=kv_len,
                     block_q=block_q, block_k=block_k, window=window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)   # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0])
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, D]

    live = _tile_live(sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0], causal,
                      window)
    live = _layout_live(live, l_ref, i, j)
    if skip_offset is not None:
        live = jnp.logical_and(
            (i + 1) * block_q - 1 + skip_offset >= j * block_k, live)

    @pl.when(live)
    def _():
        compute()

    @pl.when(i == num_q_blocks - 1)
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dbias_kernel(*refs, scale, causal, skip_offset, q_len, kv_len,
                  block_q, block_k, num_replicas, rep_h, use_alibi, window,
                  has_kbias):
    """Reduced-dbias backward for BROADCAST pair biases: grid
    (bb, hb, i, j, r) with the replica axis r innermost-sequential, so the
    [Bb, Hb, Sq, Skv] cotangent accumulates in VMEM scratch and the full
    per-replica [B, H, Sq, Skv] tensor is never materialized in HBM (the
    evoformer case: N MSA rows share one pair bias)."""
    (inputs, b_ref, kb_ref, _) = _split_bias_refs(refs[:-2], 11, True,
                                                  has_kbias)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, sq_ref, sk_ref,
     pq_ref, pk_ref, ab_ref) = inputs
    dbias_ref, acc_scr = refs[-2:]
    i = pl.program_id(2)
    j = pl.program_id(3)
    r = pl.program_id(4)
    head = pl.program_id(1) * rep_h + r % rep_h

    @pl.when(r == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _bias(s, ab_ref, head, pq_ref[0], pk_ref[0], use_alibi)
        s = _add_biases(s, b_ref, kb_ref)
        mask = _mask(i, j, sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0],
                     causal=causal, q_len=q_len, kv_len=kv_len,
                     block_q=block_q, block_k=block_k, window=window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] += p * (dp - dl_ref[0, 0])

    live = _tile_live(sq_ref[0], sk_ref[0], pq_ref[0], pk_ref[0], causal,
                      window)
    if skip_offset is not None:
        live = jnp.logical_and(
            (i + 1) * block_q - 1 + skip_offset >= j * block_k, live)

    @pl.when(live)
    def _():
        compute()

    @pl.when(r == num_replicas - 1)
    def _():
        dbias_ref[0, 0] = acc_scr[...].astype(dbias_ref.dtype)


def _dbias_call(q, k, v, do, lse, delta, seg_q, seg_k, pos_q, pos_k, ab,
                bias, kbias, *, scale, causal, skip_offset, q_len, kv_len,
                block_q, block_k, use_alibi, window, interpret):
    """Launch the reduced-dbias kernel; returns dbias of ``bias.shape``."""
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    skv = k.shape[2]
    g = h // kvh
    bb, hb = bias.shape[0], bias.shape[1]
    rb, rh = b // bb, h // hb
    nrep = rb * rh

    def amap(fn):
        # grid (bi, hi, i, j, r) → actual (b, h) = owner of replica r
        def m(bi, hi, i, j, r):
            return fn(bi * rb + r // rh, hi * rh + r % rh, i, j)
        return m

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), amap(lambda b, h, i, j: (b, h, i, 0))),
        pl.BlockSpec((1, 1, block_k, d),
                     amap(lambda b, h, i, j: (b, h // g, j, 0))),
        pl.BlockSpec((1, 1, block_k, d),
                     amap(lambda b, h, i, j: (b, h // g, j, 0))),
        pl.BlockSpec((1, 1, block_q, d), amap(lambda b, h, i, j: (b, h, i, 0))),
        pl.BlockSpec((1, 1, block_q, 1), amap(lambda b, h, i, j: (b, h, i, 0))),
        pl.BlockSpec((1, 1, block_q, 1), amap(lambda b, h, i, j: (b, h, i, 0))),
        pl.BlockSpec((1, block_q, 1), amap(lambda b, h, i, j: (b, i, 0))),
        pl.BlockSpec((1, 1, block_k), amap(lambda b, h, i, j: (b, 0, j))),
        pl.BlockSpec((1, block_q, 1), amap(lambda b, h, i, j: (b, i, 0))),
        pl.BlockSpec((1, 1, block_k), amap(lambda b, h, i, j: (b, 0, j))),
        _alibi_spec(),
        pl.BlockSpec((1, 1, block_q, block_k),
                     lambda bi, hi, i, j, r: (bi, hi, i, j)),
    ]
    arrays = [q, k, v, do, lse, delta, seg_q, seg_k, pos_q, pos_k, ab, bias]
    if kbias is not None:
        kb = kbias.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k),
            amap(lambda b, h, i, j: (b * kb // (bb * rb), 0, j))))
        arrays.append(kbias)
    kern = functools.partial(
        _dbias_kernel, scale=scale, causal=causal, skip_offset=skip_offset,
        q_len=q_len, kv_len=kv_len, block_q=block_q, block_k=block_k,
        num_replicas=nrep, rep_h=rh, use_alibi=use_alibi, window=window,
        has_kbias=kbias is not None)
    return pl.pallas_call(
        kern,
        grid=(bb, hb, sq // block_q, skv // block_k, nrep),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, block_k),
                               lambda bi, hi, i, j, r: (bi, hi, i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, hb, sq, skv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(*arrays)


# ------------------------------------------------------------- pallas_call’s
def _alibi_spec():
    # whole [H,1] table in SMEM: blocked SMEM windows below (8,128) fail
    # Mosaic lowering, so the kernel indexes its head's slope dynamically
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _bias_specs(bias, kbias, b, h, block_q, block_k, swap_ij=False):
    """Block specs + arrays for the optional additive biases. Pair bias
    [Bb, Hb, Sq, Skv] broadcasts over batch groups / heads via its index
    map; k-row bias [Bk, Skv] broadcasts over q rows inside the kernel."""
    specs, arrays = [], []
    if bias is not None:
        bb, hb = bias.shape[0], bias.shape[1]

        def bias_map(bi, hi, i, j):
            if swap_ij:
                i, j = j, i
            return (bi * bb // b, hi * hb // h, i, j)

        specs.append(pl.BlockSpec((1, 1, block_q, block_k), bias_map))
        arrays.append(bias)
    if kbias is not None:
        kb = kbias.shape[0]

        def kb_map(bi, hi, i, j):
            if swap_ij:
                i, j = j, i
            return (bi * kb // b, 0, j)

        specs.append(pl.BlockSpec((1, 1, block_k), kb_map))
        arrays.append(kbias)
    return specs, arrays


def _fwd_call(q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias,
              layout, *,
              scale, causal, skip_offset, q_len, kv_len, block_q, block_k,
              use_alibi, window, interpret):
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    skv = k.shape[2]
    grid = (b, h, sq // block_q, skv // block_k)
    g = h // kvh
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, skip_offset=skip_offset,
        q_len=q_len, kv_len=kv_len, block_q=block_q,
        block_k=block_k, num_kv_blocks=grid[3], use_alibi=use_alibi,
        window=window, has_bias=bias is not None,
        has_kbias=kbias is not None, has_layout=layout is not None)
    b_specs, b_arrays = _bias_specs(bias, kbias, b, h, block_q, block_k)
    if layout is not None:
        b_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        b_arrays.append(layout)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j)),
            pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j)),
            _alibi_spec(),
        ] + b_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, seg_q, seg_k, pos_q, pos_k, ab, *b_arrays)


def _bwd_call(q, k, v, do, lse, delta, seg_q, seg_k, pos_q, pos_k, ab,
              bias, kbias, layout, *,
              scale, causal, skip_offset, q_len, kv_len, block_q, block_k,
              use_alibi, window, interpret):
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    skv = k.shape[2]
    g = h // kvh

    nq, nkv = sq // block_q, skv // block_k
    has_bias = bias is not None
    # broadcast pair bias (evoformer: one bias shared by N MSA rows): the
    # cotangent is produced by the dedicated reducing kernel so the full
    # per-replica [B,H,Sq,Skv] tensor never hits HBM; full-shape biases
    # emit dbias tiles straight from the dq kernel (no reduction needed)
    bias_bcast = has_bias and (bias.shape[0] < b or bias.shape[1] < h)
    emit_dbias = has_bias and not bias_bcast
    common = dict(scale=scale, causal=causal, skip_offset=skip_offset,
                  q_len=q_len, kv_len=kv_len, block_q=block_q,
                  block_k=block_k, use_alibi=use_alibi, window=window,
                  has_bias=has_bias, has_kbias=kbias is not None,
                  has_layout=layout is not None)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b, h, i, j: (b, h // g, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))
    sq_spec = pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0))
    sk_spec = pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j))

    b_specs, b_arrays = _bias_specs(bias, kbias, b, h, block_q, block_k)
    if layout is not None:
        b_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        b_arrays.append(layout)
    dq_out_specs = [pl.BlockSpec((1, 1, block_q, d),
                                 lambda b, h, i, j: (b, h, i, 0))]
    dq_out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32)]
    if emit_dbias:
        dq_out_specs.append(pl.BlockSpec((1, 1, block_q, block_k),
                                         lambda b, h, i, j: (b, h, i, j)))
        dq_out_shape.append(
            jax.ShapeDtypeStruct((b, h, sq, skv), jnp.float32))
    dq_outs = pl.pallas_call(
        functools.partial(_dq_kernel, num_kv_blocks=nkv,
                          emit_dbias=emit_dbias, **common),
        grid=(b, h, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  sq_spec, sk_spec, sq_spec, sk_spec, _alibi_spec()]
        + b_specs,
        out_specs=dq_out_specs,
        out_shape=dq_out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta, seg_q, seg_k, pos_q, pos_k, ab, *b_arrays)
    if emit_dbias:
        dq, dbias = dq_outs
    else:
        (dq,), dbias = dq_outs, None
    if bias_bcast:
        if layout is not None:
            raise NotImplementedError(
                "block-sparse layouts with broadcast pair biases are not "
                "supported together")
        dbias = _dbias_call(q, k, v, do, lse, delta, seg_q, seg_k, pos_q,
                            pos_k, ab, bias, kbias, scale=scale,
                            causal=causal, skip_offset=skip_offset,
                            q_len=q_len, kv_len=kv_len, block_q=block_q,
                            block_k=block_k, use_alibi=use_alibi,
                            window=window, interpret=interpret)

    # grid reordered: kv block outer, q block inner (sequential accumulation)
    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda b, h, j, i: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_k, d),
                            lambda b, h, j, i: (b, h // g, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, j, i: (b, h, i, 0))
    sq_spec2 = pl.BlockSpec((1, block_q, 1), lambda b, h, j, i: (b, i, 0))
    sk_spec2 = pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, j))
    dkv_out = pl.BlockSpec((1, 1, block_k, d),
                           lambda b, h, j, i: (b, h, j, 0))
    ab_spec2 = _alibi_spec()
    b_specs2, b_arrays2 = _bias_specs(bias, kbias, b, h, block_q, block_k,
                                      swap_ij=True)
    if layout is not None:
        b_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        b_arrays2.append(layout)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, **common),
        grid=(b, h, nkv, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2,
                  sq_spec2, sk_spec2, sq_spec2, sk_spec2, ab_spec2]
        + b_specs2,
        out_specs=[dkv_out, dkv_out],
        out_shape=[jax.ShapeDtypeStruct((b, h, skv, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, skv, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta, seg_q, seg_k, pos_q, pos_k, ab, *b_arrays2)
    if g > 1:
        dk = dk.reshape(b, kvh, g, skv, d).sum(axis=2)
        dv = dv.reshape(b, kvh, g, skv, d).sum(axis=2)
    return dq, dk, dv, dbias


# ----------------------------------------------------------------- custom_vjp
# Names on the two residuals only the forward KERNEL can make, so that a
# ``jax.checkpoint`` policy can keep them (``models/remat.py``): a Pallas call
# is not a dot, and a backward pass that lacks them runs the forward kernel a
# second time. Inert without a policy that names them.
FLASH_RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _named_residuals(o, lse):
    return tuple(checkpoint_name(x, n)
                 for x, n in zip((o, lse), FLASH_RESIDUAL_NAMES))


@functools.lru_cache(maxsize=None)
def _make_flash(head_dim, causal, skip_offset, q_len, kv_len, block_q,
                block_k, use_alibi, window, has_bias, has_kbias, has_layout,
                interpret):
    call_kw = dict(scale=1.0 / np.sqrt(head_dim), causal=causal,
                   skip_offset=skip_offset, q_len=q_len, kv_len=kv_len,
                   block_q=block_q, block_k=block_k, use_alibi=use_alibi,
                   window=window, interpret=interpret)

    def split(bias, kbias, layout):
        return (bias if has_bias else None, kbias if has_kbias else None,
                layout if has_layout else None)

    @jax.custom_vjp
    def f(q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias, layout):
        o, _ = _fwd_call(q, k, v, seg_q, seg_k, pos_q, pos_k, ab,
                         *split(bias, kbias, layout), **call_kw)
        return o

    def f_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias, layout):
        o, lse = _named_residuals(*_fwd_call(
            q, k, v, seg_q, seg_k, pos_q, pos_k, ab,
            *split(bias, kbias, layout), **call_kw))
        return o, (q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias,
                   layout, o, lse)

    def f_bwd(res, do):
        (q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias, layout, o,
         lse) = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)            # [B,H,Sq,1]
        dq, dk, dv, dbias = _bwd_call(q, k, v, do, lse, delta, seg_q, seg_k,
                                      pos_q, pos_k, ab,
                                      *split(bias, kbias, layout),
                                      **call_kw)
        zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        # _bwd_call returns dbias already in the bias's (broadcast) shape —
        # the reducing kernel handles replicated batch/head groups in VMEM
        dbias = (dbias.astype(bias.dtype) if dbias is not None
                 else jnp.zeros_like(bias))
        # the k-row (mask) bias is non-differentiable by design — matching
        # the role it plays in the evoformer API (a -inf validity mask)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                zero(seg_q), zero(seg_k), zero(pos_q), zero(pos_k),
                jnp.zeros_like(ab), dbias, jnp.zeros_like(kbias),
                zero(layout))

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _make_flash_lse(head_dim, causal, skip_offset, q_len, kv_len, block_q,
                    block_k, use_alibi, window, has_bias, has_kbias,
                    has_layout, interpret):
    """Variant returning ``(o, lse)`` with BOTH differentiable — the block
    combiner ring attention needs (per-block outputs merge by logsumexp,
    so the final output depends on each block's lse). The backward is the
    standard flash backward with one substitution: with an lse cotangent
    ``dlse``, ``∂lse_i/∂S_ij = P_ij`` adds ``dlse_i·P_ij`` to ``dS``, i.e.
    ``dS_ij = P_ij(do_i·v_j − (δ_i − dlse_i))`` — so the kernels run
    unchanged with ``delta − dlse`` in delta's slot (dv has no lse term:
    ``∂lse/∂V = 0``)."""
    call_kw = dict(scale=1.0 / np.sqrt(head_dim), causal=causal,
                   skip_offset=skip_offset, q_len=q_len, kv_len=kv_len,
                   block_q=block_q, block_k=block_k, use_alibi=use_alibi,
                   window=window, interpret=interpret)

    def split(bias, kbias, layout):
        return (bias if has_bias else None, kbias if has_kbias else None,
                layout if has_layout else None)

    @jax.custom_vjp
    def f(q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias, layout):
        return _fwd_call(q, k, v, seg_q, seg_k, pos_q, pos_k, ab,
                         *split(bias, kbias, layout), **call_kw)

    def f_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias, layout):
        o, lse = _named_residuals(*_fwd_call(
            q, k, v, seg_q, seg_k, pos_q, pos_k, ab,
            *split(bias, kbias, layout), **call_kw))
        return (o, lse), (q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias,
                          kbias, layout, o, lse)

    def f_bwd(res, cts):
        (q, k, v, seg_q, seg_k, pos_q, pos_k, ab, bias, kbias, layout, o,
         lse) = res
        do, dlse = cts
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)            # [B,H,Sq,1]
        delta = delta - dlse.astype(jnp.float32)
        dq, dk, dv, dbias = _bwd_call(q, k, v, do, lse, delta, seg_q, seg_k,
                                      pos_q, pos_k, ab,
                                      *split(bias, kbias, layout),
                                      **call_kw)
        zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        dbias = (dbias.astype(bias.dtype) if dbias is not None
                 else jnp.zeros_like(bias))
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                zero(seg_q), zero(seg_k), zero(pos_q), zero(pos_k),
                jnp.zeros_like(ab), dbias, jnp.zeros_like(kbias),
                zero(layout))

    f.defvjp(f_fwd, f_bwd)
    return f


# -------------------------------------------------------------------- public
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True,
                    segment_ids: Optional[jnp.ndarray] = None,
                    kv_segment_ids: Optional[jnp.ndarray] = None,
                    q_positions: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    alibi: Optional[jnp.ndarray] = None,
                    window: Optional[int] = None,
                    bias: Optional[jnp.ndarray] = None,
                    k_bias: Optional[jnp.ndarray] = None,
                    block_layout: Optional[jnp.ndarray] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False) -> jnp.ndarray:
    """Flash attention over ``q [B,Sq,H,D]``, ``k/v [B,Skv,KVH,D]``.

    Differentiable (custom fwd/bwd Pallas kernels); GQA when ``KVH < H``;
    ``segment_ids [B,Sq]`` masks attention across packed-sequence
    boundaries. For ragged cross-attention (the v2 packed-KV prefill path)
    pass ``kv_segment_ids [B,Skv]`` plus explicit ``q_positions [B,Sq]`` /
    ``kv_positions [B,Skv]`` — causality then compares in-sequence
    positions instead of array indices. ``alibi``: per-head slopes [H]
    (BLOOM positional scheme, biasing logits by slope·(k_pos − q_pos));
    ``window``: sliding-window local attention (Mistral), with dead tiles
    outside the window skipped on the MXU. ``bias``: additive logit bias
    ``[Bb, Hb, Sq, Skv]`` with ``Bb | B`` and ``Hb | H`` broadcast over
    contiguous groups — differentiable (the EvoformerAttention pair bias);
    ``k_bias``: per-key row bias ``[Bk, Skv]`` broadcast over q rows and
    heads — NON-differentiable (the evoformer mask-bias role).
    ``block_layout``: static block-sparsity mask ``[Hl, ⌈Sq/block_q⌉,
    ⌈Skv/block_k⌉]`` int (0 = dead block, skipped on the MXU), ``Hl`` ∈
    {1, H} — the SparsityConfig layout contract (see
    ``ops/sparse_attention.py``). Returns ``[B,Sq,H,D]`` in q's dtype.
    Off-TPU runs in interpret mode.

    ``return_lse=True`` additionally returns the per-row logsumexp
    ``[B,Sq,H]`` fp32 (``m + log l``; ``-1e30`` for a fully-masked row) —
    differentiable alongside the output, which is what the ring-attention
    block combiner needs to merge per-block partial results exactly.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window is not None and not causal:
        # the window bound is one-sided (pos_q - pos_k < window): it limits
        # how far back a query sees but places no bound on future keys, so
        # with causal=False it would silently permit unbounded attention to
        # the future — reject rather than guess the caller's intent
        raise ValueError("window requires causal=True (the sliding window "
                         "only bounds attention to the past)")
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    if h % kvh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    offset = skv - sq
    custom_pos = q_positions is not None or kv_positions is not None
    # the static diagonal tile-skip is only sound for default positions
    skip_offset = offset if (causal and not custom_pos) else None

    # block sizes clamp to the (padded) sequence
    block_q = min(block_q, _round_up(sq, 128))
    block_k = min(block_k, _round_up(skv, 128))
    sq_p, skv_p = _round_up(sq, block_q), _round_up(skv, block_k)
    d_p = _round_up(d, _LANES)

    def pad(x, s_to, axis_s):
        cfg = [(0, 0)] * 4
        cfg[axis_s] = (0, s_to - x.shape[axis_s])
        cfg[3] = (0, d_p - d)
        return jnp.pad(x, cfg) if any(p != (0, 0) for p in cfg) else x

    qt = pad(jnp.transpose(q, (0, 2, 1, 3)), sq_p, 2)     # [B,H,Sq,D]
    kt = pad(jnp.transpose(k, (0, 2, 1, 3)), skv_p, 2)    # [B,KVH,Skv,D]
    vt = pad(jnp.transpose(v, (0, 2, 1, 3)), skv_p, 2)

    if segment_ids is None and kv_segment_ids is None:
        seg_q = jnp.zeros((b, sq_p, 1), jnp.int32)
        seg_k = jnp.zeros((b, 1, skv_p), jnp.int32)
    else:
        if kv_segment_ids is not None:
            if segment_ids is None or segment_ids.shape[1] != sq or \
                    kv_segment_ids.shape[1] != skv:
                raise ValueError("kv_segment_ids needs segment_ids [B,Sq] "
                                 "and kv_segment_ids [B,Skv]")
            sq_ids = segment_ids.astype(jnp.int32)
            sk_ids = kv_segment_ids.astype(jnp.int32)
        elif segment_ids.shape[1] == sq == skv:
            sq_ids = sk_ids = segment_ids.astype(jnp.int32)
        else:
            raise ValueError("segment_ids requires Sq == Skv == ids length")
        # pad kv segments with -1 so pad slots match no real segment
        seg_q = jnp.pad(sq_ids, ((0, 0), (0, sq_p - sq)),
                        constant_values=-2)[:, :, None]
        seg_k = jnp.pad(sk_ids, ((0, 0), (0, skv_p - skv)),
                        constant_values=-1)[:, None, :]

    if q_positions is None:
        q_pos = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32) + offset,
                                 (b, sq))
    else:
        q_pos = q_positions.astype(jnp.int32)
    if kv_positions is None:
        kv_pos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32), (b, skv))
    else:
        kv_pos = kv_positions.astype(jnp.int32)
    # pad kv positions huge so a pad slot is never <= any real q position
    pos_q = jnp.pad(q_pos, ((0, 0), (0, sq_p - sq)))[:, :, None]
    pos_k = jnp.pad(kv_pos, ((0, 0), (0, skv_p - skv)),
                    constant_values=2**30)[:, None, :]

    if alibi is not None:
        ab = jnp.asarray(alibi, jnp.float32).reshape(h, 1)
    else:
        ab = jnp.zeros((h, 1), jnp.float32)
    if bias is not None:
        bb, hb = bias.shape[0], bias.shape[1]
        if bias.shape[2:] != (sq, skv) or b % bb or h % hb:
            raise ValueError(f"bias shape {bias.shape} incompatible with "
                             f"q/kv ({b},{h},{sq},{skv})")
        bias_p = jnp.pad(bias, ((0, 0), (0, 0), (0, sq_p - sq),
                                (0, skv_p - skv)))
    else:
        bias_p = jnp.zeros((1, 1), jnp.float32)  # unused placeholder
    if k_bias is not None:
        if k_bias.shape[1] != skv or b % k_bias.shape[0]:
            raise ValueError(f"k_bias shape {k_bias.shape} incompatible "
                             f"with kv ({b},{skv})")
        # carried as [Bk, 1, Skv]: Mosaic requires the second-to-last block
        # dim be 8-divisible or full — a batch window of 1 over Bk>1 is
        # neither, so the batch axis must sit outside the last two dims
        kbias_p = jnp.pad(k_bias, ((0, 0), (0, skv_p - skv)))[:, None, :]
    else:
        kbias_p = jnp.zeros((1, 1, 1), jnp.float32)  # unused placeholder
    if block_layout is not None:
        nq_b, nkv_b = sq_p // block_q, skv_p // block_k
        if (block_layout.ndim != 3 or block_layout.shape[0] not in (1, h)
                or block_layout.shape[1:] != (nq_b, nkv_b)):
            raise ValueError(
                f"block_layout shape {block_layout.shape} must be "
                f"[1|{h}, {nq_b}, {nkv_b}] for the padded block grid")
        if bias is not None and (bias.shape[0] < b or bias.shape[1] < h):
            # reject at the API boundary, not deep inside the backward: the
            # reduced-dbias kernel does not consume block layouts
            raise NotImplementedError(
                "block_layout with a BROADCAST differentiable bias is not "
                "supported (the reduced-dbias kernel ignores layouts); use "
                "a full-shape bias or drop the layout")
        layout_a = jnp.asarray(block_layout, jnp.int32)
    else:
        layout_a = jnp.zeros((1, 1, 1), jnp.int32)  # unused placeholder
    maker = _make_flash_lse if return_lse else _make_flash
    fn = maker(int(d), bool(causal),
               None if skip_offset is None else int(skip_offset),
               int(sq), int(skv), int(block_q), int(block_k),
               alibi is not None,
               None if window is None else int(window),
               bias is not None, k_bias is not None,
               block_layout is not None,
               bool(interpret))
    out = fn(qt, kt, vt, seg_q, seg_k, pos_q, pos_k, ab, bias_p,
             kbias_p, layout_a)                           # [B,H,Sq_p,D_p]
    if return_lse:
        out, lse = out
        out = jnp.transpose(out[:, :, :sq, :d], (0, 2, 1, 3))
        return out, jnp.transpose(lse[:, :, :sq, 0], (0, 2, 1))
    out = out[:, :, :sq, :d]
    return jnp.transpose(out, (0, 2, 1, 3))
