"""Pallas flash attention — fused streaming-softmax attention, fwd + bwd.

The training/prefill attention kernel: the TPU-native answer to the
reference's fused-attention native code (v1 inference fused softmax/attention
``csrc/transformer/inference/csrc/``, the CUTLASS EvoformerAttention family
``csrc/deepspeed4science/evoformer_attn/`` ~14.9k LoC, and v2's
``blocked_flash``). One kernel family, three Pallas kernels in a train step:

* forward: grid (batch, q_head, q_block, kv_block) with the kv dimension
  innermost-sequential; online-softmax state (m, l, acc) lives in VMEM
  scratch that persists across the kv sweep, so logits are never
  materialized in HBM — O(S) memory vs the O(S²) jnp reference. Returns
  ``o [B,H,S,D]`` and the per-row logsumexp as lane-dense ROWS, one a
  compute tile: ``lse [B,H,S/tile,1,tile]`` (never ``o``'s shape).
* backward: the standard two-kernel split, recomputing probabilities from the
  saved logsumexp (flash-attention-2 style), wired as a ``jax.custom_vjp``.
  dQ accumulates over kv blocks on the ``q kᵀ`` tile. dK/dV accumulate over
  the q blocks AND over the query heads of a kv head's group (grid (batch,
  kv_head, kv_block, head_in_group, q_block), the last two sequential) on the
  TRANSPOSED tile ``k qᵀ``: both accumulations are plain ``[bk, bq] x
  [bq, d]`` products, ``lse`` / ``delta`` broadcast along sublanes, K and V
  are fetched once a group, and dK / dV leave the kernel summed, ``[B,KVH,S,D]``
  in k's dtype.

**A grid step does only what its tiles must.** A step copies one ``block_q x
block_k`` block (up to 4096 a side by the rule) and walks it as 512 x 512
compute tiles in one rolled loop (:func:`_run_tiles`). Every tile is one of three
kinds, decided from what the tile can observe (:func:`_static_kind` from the
tile's indices under default positions, :func:`_dynamic_kind` from the
min / max of its segment and position blocks otherwise):

* DEAD (over the causal diagonal, outside the window, no segment in common,
  a dead layout block): no product; and under default positions no copy
  either, because the streamed operand's block index is clamped to the row's
  (column's) live range and an unchanged index issues no DMA;
* INTERIOR (every pair valid): two or three products and the softmax — no
  iota, no compare, no select;
* BOUNDARY: the same with a mask made of only the compares that can bite
  (``q_len`` / ``kv_len`` only in a padded last block, the window only if it
  is shorter than the context, segments and explicit positions only if the
  caller passed any).

What is per row or per block is done per row or per block: ``1/√d`` goes into
the ``[block, d]`` operand once a q block (forward, dQ) or once a kv block
(dK/dV) and onto the float32 accumulators at the end; operands reach the MXU
in their own dtype (Mosaic rounds a float32 copy of a bf16 operand back to
bf16 at the default precision: same bits, same time); ``lse`` and ``delta``
travel as lane-dense rows, one a compute tile, and dQ turns them into columns
once a q block. Block sizes a caller does not pass come from one rule,
:func:`_default_blocks`; :func:`tile_plan` counts a call's tiles by kind.

Masking supports causal (with Sq != Skv offsets), packed-sequence
``segment_ids``, and length padding (sequences pad to block multiples, the
pad region is masked). Causality compares POSITIONS: for plain attention the
(offset-shifted) indices, and for the ragged packed-KV prefill path
(``inference/v2/model.py``) explicit per-token arrays, so that many
variable-context sequences run in one call: q tokens carry their position
within their own sequence, the packed KV carries per-slot positions, and
separate q/kv segment ids bound each sequence. Off-TPU the kernels run in
interpret mode, which is also how the parity tests exercise them (SURVEY.md
§4 pattern).
"""
import functools
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the package's logger by its name (``utils/logging.py``): this module imports
# nothing of the package, so a second checkout's copy loads beside it
# (``tools/tpu_tune.py flash``)
logger = logging.getLogger("dstpu")

NEG_INF = -1e30
_LANES = 128
_DEFAULT_SCOPED_VMEM = 16 << 20   # Mosaic's scoped-VMEM default on the v5e
_VMEM_CAP = 100 << 20             # of the v5e's 128 MiB

__all__ = ["flash_attention", "tile_plan"]

_NT = (((1,), (1,)), ((), ()))    # a bᵀ: contract both operands' last axis


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class _Tile(NamedTuple):
    """What is static about one kernel's tiles. ``window`` is None also when
    it cannot bite (default positions, ``window >= Skv``); ``offset`` is the
    default q position of row 0 (``Skv - Sq``); ``sub_q`` / ``sub_k`` divide
    ``block_q`` / ``block_k``."""
    scale: float
    causal: bool
    offset: int
    custom_pos: bool
    has_seg: bool
    q_len: int
    kv_len: int
    block_q: int
    block_k: int
    nq: int
    nkv: int
    use_alibi: bool
    window: Optional[int]
    has_bias: bool
    has_kbias: bool
    has_layout: bool
    sub_q: int
    sub_k: int

    @property
    def compute(self):
        """The same facts at the COMPUTE tile: a grid step's ``block_q x
        block_k`` block (what the pipeline copies) is walked as ``sub_q x
        sub_k`` tiles, each of its own kind."""
        return self._replace(block_q=self.sub_q, block_k=self.sub_k,
                             nq=self.nq * self.block_q // self.sub_q,
                             nkv=self.nkv * self.block_k // self.sub_k)

    @property
    def static_diag(self):
        """Default-position causal: liveness is arithmetic on (i, j)."""
        return self.causal and not self.custom_pos

    @property
    def q_pad(self):
        return self.nq * self.block_q != self.q_len

    @property
    def kv_pad(self):
        return self.nkv * self.block_k != self.kv_len


# ------------------------------------------------ python-or-traced booleans
def _and(a, b):
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return jnp.logical_and(a, b)


def _not(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


def _when(cond, fn):
    """``pl.when`` that folds a condition known at trace time."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


# --------------------------------------------------------- the kind of a tile
def _static_kind(t, i, j):
    """``(live, interior)`` of tile (i, j) from its indices alone — python
    bools where the call's static facts decide, scalars otherwise. ``live``
    False: no pair of the tile is valid. ``interior`` True: no pair can be
    cut by the diagonal, the window or the padding (segments and explicit
    positions are :func:`_dynamic_kind`'s)."""
    live = interior = True
    if t.static_diag:
        q_lo = i * t.block_q + t.offset        # positions of the tile's rows
        q_hi = q_lo + t.block_q - 1
        k_lo = j * t.block_k
        k_hi = k_lo + t.block_k - 1
        live, interior = q_hi >= k_lo, k_hi <= q_lo
        if t.window is not None:
            live = _and(live, q_lo - k_hi < t.window)
            interior = _and(interior, q_hi - k_lo < t.window)
    if t.q_pad:
        interior = _and(interior, i != t.nq - 1)
    if t.kv_pad:
        interior = _and(interior, j != t.nkv - 1)
    return live, interior


def _dynamic_kind(t, seg_q, seg_k, pos_q, pos_k):
    """``(live, interior)`` from the tile's segment / position blocks: dead
    when no q/kv segment pair can match, or (position-causal) every kv
    position exceeds every q position, or (window) every kv position is below
    every q position's window; interior when both sides are ONE segment, the
    same, and every kv position is visible to every q position. This is what
    keeps the packed ragged-prefill path O(tokens x own-context) in compute
    even though the kv stream is the whole packed pool."""
    live = interior = True
    if t.has_seg:
        q_lo, q_hi = jnp.min(seg_q), jnp.max(seg_q)
        k_lo, k_hi = jnp.min(seg_k), jnp.max(seg_k)
        live = jnp.logical_and(k_lo <= q_hi, k_hi >= q_lo)
        interior = jnp.logical_and(jnp.logical_and(q_lo == q_hi, k_lo == k_hi),
                                   q_lo == k_lo)
    if t.custom_pos and t.causal:
        q_lo, q_hi = jnp.min(pos_q), jnp.max(pos_q)
        k_lo, k_hi = jnp.min(pos_k), jnp.max(pos_k)
        live = _and(live, k_lo <= q_hi)
        interior = _and(interior, k_hi <= q_lo)
        if t.window is not None:
            live = _and(live, q_lo - k_hi < t.window)
            interior = _and(interior, q_hi - k_lo < t.window)
    return live, interior


def _mask(t, i, j, seg_q, seg_k, pos_q, pos_k, q_axis):
    """Validity mask of a BOUNDARY tile, from only the compares that can
    bite; q runs along axis ``q_axis`` (1 on dK/dV's transposed tile) and the
    q-side / kv-side vectors arrive shaped to broadcast along the other.
    Under default positions causality is ``k_idx - q_idx <= threshold`` on one
    iota difference; explicit positions compare the arrays (``window`` adds
    the Mistral-style bound: q sees the last ``window`` positions)."""
    shape = ((t.block_q, t.block_k) if q_axis == 0
             else (t.block_k, t.block_q))
    m = None

    def both(m, c):
        return c if m is None else jnp.logical_and(m, c)

    if t.static_diag or t.q_pad or t.kv_pad:
        q_loc = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        k_loc = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if t.q_pad:
        m = both(m, q_loc < t.q_len - i * t.block_q)
    if t.kv_pad:
        m = both(m, k_loc < t.kv_len - j * t.block_k)
    if t.static_diag:
        rel = k_loc - q_loc
        thr = i * t.block_q + t.offset - j * t.block_k   # pos_q-pos_k=thr-rel
        m = both(m, rel <= thr)
        if t.window is not None:
            m = both(m, rel > thr - t.window)
    elif t.causal:
        m = both(m, pos_k <= pos_q)
        if t.window is not None:
            m = both(m, pos_q - pos_k < t.window)
    if t.has_seg:
        m = both(m, seg_q == seg_k)
    return m


def _side_vectors(t, r, i, j, rows, keys, q_axis, want_pos):
    """The tile's (seg_q, seg_k, pos_q, pos_k), each None where the call has
    none; default positions are made from an iota only if ``want_pos``.
    ``rows`` / ``keys`` are the tile's slices of the step's blocks."""
    seg_q = seg_k = pos_q = pos_k = None

    def q_vec(name):
        return r[name][0, rows, :] if q_axis == 0 else r[name][0, :, rows]

    def k_vec(name):
        return r[name][0, :, keys] if q_axis == 0 else r[name][0, keys, :]

    if t.has_seg:
        seg_q, seg_k = q_vec("seg_q"), k_vec("seg_k")
    if t.custom_pos:
        pos_q, pos_k = q_vec("pos_q"), k_vec("pos_k")
    elif want_pos:
        q_shape = (t.block_q, 1) if q_axis == 0 else (1, t.block_q)
        k_shape = (1, t.block_k) if q_axis == 0 else (t.block_k, 1)
        pos_q = (i * t.block_q + t.offset
                 + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis))
        pos_k = (j * t.block_k
                 + jax.lax.broadcasted_iota(jnp.int32, k_shape, 1 - q_axis))
    return seg_q, seg_k, pos_q, pos_k


def _scores(t, r, i, j, rows, keys, head, lhs, rhs, masked, q_axis):
    """The tile's biased scores and its mask (None on an interior tile).
    ALiBi adds ``slope·(k_pos − q_pos)`` (the [H,1] slope table sits whole in
    SMEM: Mosaic rejects sub-(8,128) blocked windows even there); the
    additive biases follow the EvoformerAttention pattern (reference
    ``csrc/deepspeed4science/evoformer_attn/``): a pair-bias tile and / or a
    per-key row bias, both added AFTER the 1/√d scaling. None of them is a
    mask: both kinds of live tile carry them."""
    s = jax.lax.dot_general(lhs, rhs, _NT, preferred_element_type=jnp.float32)
    seg_q, seg_k, pos_q, pos_k = _side_vectors(t, r, i, j, rows, keys,
                                               q_axis, t.use_alibi)
    if t.use_alibi:
        s = s + r["ab"][head, 0] * (pos_k - pos_q).astype(jnp.float32)
    if t.has_bias:
        b = r["bias"][0, 0, rows, keys].astype(jnp.float32)
        s = s + (b if q_axis == 0 else b.T)
    if t.has_kbias:   # [1,bk] / [bk,1]
        kb = (r["kbias"][0, :, keys] if q_axis == 0
              else r["kbias"][0, keys, :])
        s = s + kb.astype(jnp.float32)
    mask = (_mask(t, i, j, seg_q, seg_k, pos_q, pos_k, q_axis)
            if masked else None)
    return s, mask


def _run_tiles(t, r, i, j, head, compute, q_axis=0, on_dead=None):
    """Walk grid step (i, j)'s block as compute tiles and run
    ``compute(c, ci, cj, a, rows, keys, masked)`` on each as its kind asks:
    not at all on a dead tile (``on_dead(rows, keys)`` there, if given),
    unmasked on an interior one. ``c`` is the compute-tile view of ``t``,
    (ci, cj) the tile's coordinates in it, ``a`` its q index within the step,
    ``rows`` / ``keys`` its slices of the step's blocks. A step of several
    tiles is ONE rolled loop (q-major, or kv-major on the transposed tile,
    ``q_axis`` 1), traced once whatever the step holds: a tile's slices are
    then dynamic, which Mosaic takes along sublanes and leading axes only, so
    :func:`flash_attention` keeps step and tile the same for a call with
    per-key rows or per-pair tiles (segments, explicit positions, biases)."""
    c = t.compute
    rq, rk = t.block_q // t.sub_q, t.block_k // t.sub_k
    if rq == rk == 1:
        return _run_tile(c, r, i, j, 0, slice(0, t.sub_q), slice(0, t.sub_k),
                         head, compute, q_axis, on_dead)

    def tile(a, b):
        _run_tile(c, r, i * rq + a, j * rk + b, a,
                  pl.ds(pl.multiple_of(a * t.sub_q, t.sub_q), t.sub_q),
                  pl.ds(pl.multiple_of(b * t.sub_k, t.sub_k), t.sub_k),
                  head, compute, q_axis, on_dead)

    def loop(n, body):
        jax.lax.fori_loop(0, n, lambda x, _: body(x), None)

    if q_axis == 0:
        loop(rq, lambda a: loop(rk, lambda b: tile(a, b)))
    else:
        loop(rk, lambda b: loop(rq, lambda a: tile(a, b)))


def _run_tile(c, r, ci, cj, a, rows, keys, head, compute, q_axis, on_dead):
    """The index arithmetic and the layout's scalar come first; the blocks'
    reductions run only on a tile those leave alive."""
    live, interior = _static_kind(c, ci, cj)
    if c.has_layout:
        # a static block-sparsity layout (the reference's SparsityConfig,
        # ``ops/sparse_attention/sparsity_config.py``) [Hl, nq, nkv] whole in
        # SMEM: Hl == H per-head layouts, Hl == 1 one shared by the heads
        lay = r["layout"]
        live = _and(live, lay[head if lay.shape[0] > 1 else 0, ci, cj] != 0)

    def run(masked):
        return lambda: compute(c, ci, cj, a, rows, keys, masked)

    def dead():
        on_dead(rows, keys)

    def inner():
        dyn_live, dyn_interior = True, True
        if c.has_seg or c.custom_pos:
            dyn_live, dyn_interior = _dynamic_kind(
                c, *_side_vectors(c, r, ci, cj, rows, keys, q_axis, False))
        inside = _and(interior, dyn_interior)
        _when(_and(dyn_live, inside), run(False))
        _when(_and(dyn_live, _not(inside)), run(True))
        if on_dead is not None:
            _when(_not(dyn_live), dead)

    _when(live, inner)
    if on_dead is not None:
        _when(_not(live), dead)


def _lanes(x, n):
    """A lane-replicated ``[rows, 128]`` column as ``[rows, n]``: whole
    vregs repeated, no broadcast."""
    if n == x.shape[1]:
        return x
    if n % x.shape[1] == 0:
        return jnp.tile(x, (1, n // x.shape[1]))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _col_to_row(x):
    """``[rows, 128]`` lane-replicated column -> the same values as a
    lane-dense ``[1, rows]`` row."""
    return x.T[:1]


def _row_to_col(x):
    """``[1, rows]`` row -> ``[rows, 128]`` lane-replicated column."""
    return jnp.broadcast_to(x, (_LANES, x.shape[1])).T


def _scaled(x, scale):
    """``x * scale`` in x's dtype: one rounding of the float32 product."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _named(names, refs):
    return dict(zip(names, refs)), refs[len(names):]


# ------------------------------------------------------------------- forward
def _fwd_kernel(*refs, t, names):
    r, (o_ref, lse_ref, qs_scr, m_scr, l_scr, acc_scr) = _named(names, refs)
    h = pl.program_id(1)  # hoisted: program_id must not sit inside pl.when
    i = pl.program_id(2)
    j = pl.program_id(3)
    d = acc_scr.shape[1]

    @pl.when(j == 0)
    def _():
        qs_scr[...] = _scaled(r["q"][0, 0], t.scale)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(c, ci, cj, a, rows, keys, masked):
        v = r["v"][0, 0, keys, :]
        s, mask = _scores(c, r, ci, cj, rows, keys, h, qs_scr[rows, :],
                          r["k"][0, 0, keys, :], masked, 0)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[rows, :]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)                   # [bq, LANES]
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        if mask is not None:
            # masked-out entries must stay 0 even when the whole row is
            # masked (NEG_INF - NEG_INF == 0 would otherwise exp to 1)
            p = jnp.where(mask, p, 0.0)
        l_scr[rows, :] = (l_scr[rows, :] * alpha
                          + jnp.sum(p, axis=1, keepdims=True))
        m_scr[rows, :] = m_next
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_scr[rows, :] = acc_scr[rows, :] * _lanes(alpha, d) + pv

    _run_tiles(t, r, i, j, h, compute)

    @pl.when(j == t.nkv - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / _lanes(l, d)).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)
        for a in range(t.block_q // t.sub_q):    # one lane-dense row a tile
            lse_ref[0, 0, a] = _col_to_row(lse[a * t.sub_q:(a + 1) * t.sub_q])


# ------------------------------------------------------------------ backward
def _bwd_tile(t, r, i, j, rows, keys, head, lhs, rhs, dlhs, drhs, lse, delta,
              masked, q_axis):
    """``(p, ds)`` of one tile: probabilities recomputed from ``lse``, and
    ``ds = p·(dp − delta)`` with ``dp = dlhs drhsᵀ``."""
    s, mask = _scores(t, r, i, j, rows, keys, head, lhs, rhs, masked, q_axis)
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(dlhs, drhs, _NT,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _dq_kernel(*refs, t, names, emit_dbias):
    r, outs = _named(names, refs)
    if emit_dbias:
        dq_ref, dbias_ref, qs_scr, lse_scr, dl_scr, dq_scr = outs
    else:
        (dq_ref, qs_scr, lse_scr, dl_scr, dq_scr), dbias_ref = outs, None
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        qs_scr[...] = _scaled(r["q"][0, 0], t.scale)
        for a in range(t.block_q // t.sub_q):
            at = slice(a * t.sub_q, (a + 1) * t.sub_q)
            lse_scr[at, :] = _row_to_col(r["lse"][0, 0, a])
            dl_scr[at, :] = _row_to_col(r["delta"][0, 0, a])
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute(c, ci, cj, a, rows, keys, masked):
        k = r["k"][0, 0, keys, :]
        _, ds = _bwd_tile(c, r, ci, cj, rows, keys, h, qs_scr[rows, :], k,
                          r["do"][0, 0, rows, :], r["v"][0, 0, keys, :],
                          _lanes(lse_scr[rows, :], c.block_k),
                          _lanes(dl_scr[rows, :], c.block_k), masked, 0)
        if dbias_ref is not None:
            # s = scaled-qk + bias ⇒ ∂L/∂bias tile is exactly ds
            dbias_ref[0, 0, rows, keys] = ds.astype(dbias_ref.dtype)
        dq_scr[rows, :] += jnp.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    def zero_dbias(rows, keys):   # dead tiles still own their dbias block
        dbias_ref[0, 0, rows, keys] = jnp.zeros(
            (t.sub_q, t.sub_k), dbias_ref.dtype)

    _run_tiles(t, r, i, j, h, compute,
               on_dead=zero_dbias if dbias_ref is not None else None)

    @pl.when(j == t.nkv - 1)
    def _():
        dq_ref[0, 0] = (dq_scr[...] * t.scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, t, names, group):
    r, (dk_ref, dv_ref, ks_scr, dk_scr, dv_scr) = _named(names, refs)
    kvh = pl.program_id(1)
    j = pl.program_id(2)    # kv block (outer)
    hg = pl.program_id(3)   # query head of the group   } sequential: one
    i = pl.program_id(4)    # q block                   } accumulation
    h = kvh * group + hg

    @pl.when(jnp.logical_and(hg == 0, i == 0))
    def _():
        ks_scr[...] = _scaled(r["k"][0, 0], t.scale)
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute(c, ci, cj, a, rows, keys, masked):
        q, do = r["q"][0, 0, rows, :], r["do"][0, 0, rows, :]
        # the transposed tile [bk, bq]: lse / delta rows broadcast along
        # sublanes and neither accumulation transposes a score tile
        p, ds = _bwd_tile(c, r, ci, cj, rows, keys, h, ks_scr[keys, :], q,
                          r["v"][0, 0, keys, :], do, r["lse"][0, 0, a],
                          r["delta"][0, 0, a], masked, 1)
        dv_scr[keys, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dk_scr[keys, :] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _run_tiles(t, r, i, j, h, compute, q_axis=1)

    @pl.when(jnp.logical_and(hg == group - 1, i == t.nq - 1))
    def _():
        dk_ref[0, 0] = (dk_scr[...] * t.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dbias_kernel(*refs, t, names, num_replicas, rep_h):
    """Reduced-dbias backward for BROADCAST pair biases: grid
    (bb, hb, i, j, r) with the replica axis r innermost-sequential, so the
    [Bb, Hb, Sq, Skv] cotangent accumulates in VMEM scratch and the full
    per-replica [B, H, Sq, Skv] tensor is never materialized in HBM (the
    evoformer case: N MSA rows share one pair bias). ``lse`` / ``delta``
    come as columns here: every step is another (batch, head)."""
    r, (dbias_ref, acc_scr) = _named(names, refs)
    i = pl.program_id(2)
    j = pl.program_id(3)
    rep = pl.program_id(4)
    head = pl.program_id(1) * rep_h + rep % rep_h

    @pl.when(rep == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(c, ci, cj, a, rows, keys, masked):
        _, ds = _bwd_tile(c, r, ci, cj, rows, keys, head,
                          _scaled(r["q"][0, 0, rows, :], t.scale),
                          r["k"][0, 0, keys, :], r["do"][0, 0, rows, :],
                          r["v"][0, 0, keys, :], r["lse"][0, 0, rows, :],
                          r["delta"][0, 0, rows, :], masked, 0)
        acc_scr[rows, keys] += ds

    _run_tiles(t, r, i, j, head, compute)

    @pl.when(rep == num_replicas - 1)
    def _():
        dbias_ref[0, 0] = acc_scr[...].astype(dbias_ref.dtype)


# ------------------------------------------------------------- pallas_call’s
def _live_range_clamps(t, q_axis, clamp=True):
    """``(qi, kj)``: the block indices the q-side and kv-side operands are
    fetched at in grid step (i, j). The STREAMED side (kv under a q row for
    forward and dQ, q under a kv column for dK/dV) is clamped to the row's
    (column's) live range under default positions, so that a dead step names
    the block its neighbour already holds and the pipeline copies nothing
    (``clamp`` False: a grid with no streamed side, the reduced dbias)."""
    def same_i(i, j):
        return i

    def same_j(i, j):
        return j

    if not (clamp and t.static_diag):
        return same_i, same_j
    bq, bk, w = t.block_q, t.block_k, t.window

    def kj(i, j):
        hi = jnp.maximum((i + 1) * bq - 1 + t.offset, 0) // bk
        lo = 0 if w is None else jnp.maximum(i * bq + t.offset - w + 1,
                                             0) // bk
        return jnp.clip(jnp.minimum(jnp.maximum(j, lo), hi), 0, t.nkv - 1)

    def qi(i, j):
        lo = jnp.maximum(j * bk - t.offset, 0) // bq
        hi = t.nq - 1 if w is None else jnp.maximum(
            w - t.offset + j * bk + bk - 2, 0) // bq
        return jnp.clip(jnp.minimum(jnp.maximum(i, lo), hi), 0, t.nq - 1)

    return (same_i, kj) if q_axis == 0 else (qi, same_j)


def _operands(t, given, q_axis, amap, batch, heads, group, clamp=True,
              rows=True):
    """``(names, arrays, specs)`` of a kernel's inputs, in ``given``'s order
    and only those the call's static facts use. Index maps are written over
    (b, h, i, j) and ``amap`` adapts them to the kernel's grid; ``q_axis`` 1
    lays the q-side vectors out as rows and the kv-side ones as columns (the
    transposed tile). ``rows`` False hands ``lse`` / ``delta`` as columns."""
    bq, bk = t.block_q, t.block_k
    qi, kj = _live_range_clamps(t, q_axis, clamp)
    d = given["q"].shape[-1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def spec(block, fn):
        return pl.BlockSpec(block, amap(fn))

    def q_side(x):     # [B, S] -> column or row blocks
        if q_axis == 0:
            return x[:, :, None], spec((1, bq, 1), lambda b, h, i, j: (b, i, 0))
        return x[:, None, :], spec((1, 1, bq),
                                   lambda b, h, i, j: (b, 0, qi(i, j)))

    def k_side(x, bmap=lambda b: b):
        if q_axis == 0:
            return x[:, None, :], spec(
                (1, 1, bk), lambda b, h, i, j: (bmap(b), 0, kj(i, j)))
        return x[:, :, None], spec((1, bk, 1),
                                   lambda b, h, i, j: (bmap(b), j, 0))

    q_spec = spec((1, 1, bq, d), lambda b, h, i, j: (b, h, qi(i, j), 0))
    kv_spec = spec((1, 1, bk, d),
                   lambda b, h, i, j: (b, h // group, kj(i, j), 0))
    if rows:   # [B, H, tiles, 1, sub_q]: a step's tiles, a row each
        row = lambda x: (x, spec((1, 1, bq // t.sub_q, 1, t.sub_q),
                                 lambda b, h, i, j: (b, h, qi(i, j), 0, 0)))
    else:
        row = lambda x: (x.reshape(x.shape[:2] + (-1, 1)),
                         spec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)))
    build = {"q": lambda x: (x, q_spec), "do": lambda x: (x, q_spec),
             "k": lambda x: (x, kv_spec), "v": lambda x: (x, kv_spec),
             "lse": row, "delta": row,
             "seg_q": q_side, "pos_q": q_side,
             "seg_k": k_side, "pos_k": k_side,
             "ab": lambda x: (x, smem), "layout": lambda x: (x, smem)}
    if t.has_bias:
        bb, hb = given["bias"].shape[:2]
        build["bias"] = lambda x: (x, spec(
            (1, 1, bq, bk),
            lambda b, h, i, j: (b * bb // batch, h * hb // heads, i, j)))
    if t.has_kbias:
        kb = given["kbias"].shape[0]
        build["kbias"] = lambda x: k_side(x, lambda b: b * kb // batch)
    use = {"seg_q": t.has_seg, "seg_k": t.has_seg,
           "pos_q": t.custom_pos, "pos_k": t.custom_pos,
           "ab": t.use_alibi, "bias": t.has_bias, "kbias": t.has_kbias,
           "layout": t.has_layout}
    names = [n for n in given if use.get(n, True)]
    arrays, specs = zip(*(build[n](given[n]) for n in names))
    return tuple(names), arrays, list(specs)


def _vmem_limit(block, d, itemsize):
    """Scoped VMEM a kernel asks for at ``block`` = (block_q, block_k, sub_q,
    sub_k): its double-buffered ``[block, d]`` operands (six at most: q, k,
    v, do and two results), its float32 scratch and about five live ``[sub_q,
    sub_k]`` float32 tiles (scores, probabilities, mask, dp, ds), doubled for
    what the compiler keeps besides, inside the v5e's 128 MiB."""
    bq, bk, cq, ck = block
    rows = max(bq, bk)
    need = 2 * 6 * rows * d * itemsize + 4 * rows * d * 4 + 5 * cq * ck * 4
    return min(max(2 * need, _DEFAULT_SCOPED_VMEM), _VMEM_CAP)


def _params(t, d, itemsize, semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_vmem_limit(
            (t.block_q, t.block_k, t.sub_q, t.sub_k), d, itemsize))


_PAR4 = ("parallel", "parallel", "parallel", "arbitrary")


def _fwd_call(t, q, k, v, extras, interpret):
    b, h, sq, d = q.shape
    group = h // k.shape[1]
    names, arrays, specs = _operands(t, dict(q=q, k=k, v=v, **extras), 0,
                                     lambda fn: fn, b, h, group)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t, names=names),
        grid=(b, h, t.nq, t.nkv),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, 1, t.block_q, d),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, t.block_q // t.sub_q, 1, t.sub_q),
                         lambda b, h, i, j: (b, h, i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq // t.sub_q, 1, t.sub_q),
                                        jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((t.block_q, d), q.dtype),
            pltpu.VMEM((t.block_q, _LANES), jnp.float32),
            pltpu.VMEM((t.block_q, _LANES), jnp.float32),
            pltpu.VMEM((t.block_q, d), jnp.float32),
        ],
        compiler_params=_params(t, d, q.dtype.itemsize, _PAR4),
        interpret=interpret,
    )(*arrays)


def _dq_call(t, q, k, v, do, lse, delta, extras, interpret):
    """dQ [B,H,Sq,D] in q's dtype; with a full-shape pair bias also its
    cotangent, emitted tile by tile (no reduction needed)."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    group = h // k.shape[1]
    emit_dbias = t.has_bias and extras["bias"].shape[:2] == (b, h)
    names, arrays, specs = _operands(
        t, dict(q=q, k=k, v=v, do=do, lse=lse, delta=delta, **extras), 0,
        lambda fn: fn, b, h, group)
    out_specs = [pl.BlockSpec((1, 1, t.block_q, d),
                              lambda b, h, i, j: (b, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)]
    if emit_dbias:
        out_specs.append(pl.BlockSpec((1, 1, t.block_q, t.block_k),
                                      lambda b, h, i, j: (b, h, i, j)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, skv), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_dq_kernel, t=t, names=names,
                          emit_dbias=emit_dbias),
        grid=(b, h, t.nq, t.nkv),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((t.block_q, d), q.dtype),
                        pltpu.VMEM((t.block_q, _LANES), jnp.float32),
                        pltpu.VMEM((t.block_q, _LANES), jnp.float32),
                        pltpu.VMEM((t.block_q, d), jnp.float32)],
        compiler_params=_params(t, d, q.dtype.itemsize, _PAR4),
        interpret=interpret,
    )(*arrays)
    return outs if emit_dbias else (outs[0], None)


def _dkv_call(t, q, k, v, do, lse, delta, extras, interpret):
    """dK, dV [B,KVH,Skv,D] in k's dtype, the group's query heads summed in
    the kernel's scratch."""
    b, h, _, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh

    def amap(fn):   # grid (b, kv head, kv block, head in group, q block)
        return lambda b, kv, j, hg, i: fn(b, kv * group + hg, i, j)

    names, arrays, specs = _operands(
        t, dict(q=q, k=k, v=v, do=do, lse=lse, delta=delta, **extras), 1,
        amap, b, h, group)
    out = pl.BlockSpec((1, 1, t.block_k, d),
                       lambda b, kv, j, hg, i: (b, kv, j, 0))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, t=t, names=names, group=group),
        grid=(b, kvh, t.nkv, group, t.nq),
        in_specs=specs,
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((b, kvh, skv, d), k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((t.block_k, d), k.dtype),
                        pltpu.VMEM((t.block_k, d), jnp.float32),
                        pltpu.VMEM((t.block_k, d), jnp.float32)],
        compiler_params=_params(
            t, d, q.dtype.itemsize,
            ("parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*arrays)


def _dbias_call(t, q, k, v, do, lse, delta, extras, interpret):
    """Launch the reduced-dbias kernel; returns dbias of ``bias.shape``."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    group = h // k.shape[1]
    bb, hb = extras["bias"].shape[:2]
    rb, rh = b // bb, h // hb

    def amap(fn):   # grid (bi, hi, i, j, r) → (b, h) = owner of replica r
        return lambda bi, hi, i, j, r: fn(bi * rb + r // rh,
                                          hi * rh + r % rh, i, j)

    names, arrays, specs = _operands(
        t, dict(q=q, k=k, v=v, do=do, lse=lse, delta=delta, **extras), 0,
        amap, b, h, group, clamp=False, rows=False)
    return pl.pallas_call(
        functools.partial(_dbias_kernel, t=t, names=names,
                          num_replicas=rb * rh, rep_h=rh),
        grid=(bb, hb, t.nq, t.nkv, rb * rh),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, 1, t.block_q, t.block_k),
                               lambda bi, hi, i, j, r: (bi, hi, i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, hb, sq, skv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t.block_q, t.block_k), jnp.float32)],
        compiler_params=_params(
            t, d, q.dtype.itemsize,
            ("parallel", "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*arrays)


# ----------------------------------------------------------------- custom_vjp
# Names on the two residuals only the forward KERNEL can make, so that a
# ``jax.checkpoint`` policy can keep them (``models/remat.py``): a Pallas call
# is not a dot, and a backward pass that lacks them runs the forward kernel a
# second time. Inert without a policy that names them.
FLASH_RESIDUAL_NAMES = ("flash_o", "flash_lse")

_EXTRAS = ("seg_q", "seg_k", "pos_q", "pos_k", "ab", "bias", "kbias",
           "layout")


@functools.lru_cache(maxsize=None)
def _make_flash(tile, with_lse, interpret):
    """The differentiable call for one set of static facts, ``tile``.
    ``with_lse`` returns ``(o, lse)`` with BOTH differentiable —
    the block combiner ring attention needs (per-block outputs merge by
    logsumexp, so the final output depends on each block's lse). Its backward
    is the standard flash backward with one substitution: with an lse
    cotangent ``dlse``, ``∂lse_i/∂S_ij = P_ij`` adds ``dlse_i·P_ij`` to
    ``dS``, i.e. ``dS_ij = P_ij(do_i·v_j − (δ_i − dlse_i))`` — so the kernels
    run unchanged with ``delta − dlse`` in delta's slot (dv has no lse term:
    ``∂lse/∂V = 0``)."""
    def forward(q, k, v, *extras):
        o, lse = _fwd_call(tile, q, k, v, dict(zip(_EXTRAS, extras)),
                           interpret)
        return tuple(checkpoint_name(x, n)
                     for x, n in zip((o, lse), FLASH_RESIDUAL_NAMES))

    @jax.custom_vjp
    def f(q, k, v, *extras):
        o, lse = forward(q, k, v, *extras)
        return (o, lse) if with_lse else o

    def f_fwd(q, k, v, *extras):
        o, lse = forward(q, k, v, *extras)
        return ((o, lse) if with_lse else o), (q, k, v, extras, o, lse)

    def f_bwd(res, cts):
        q, k, v, extras, o, lse = res
        do, dlse = cts if with_lse else (cts, None)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1).reshape(lse.shape)        # lse's tiled rows
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)
        ex = dict(zip(_EXTRAS, extras))
        dq, dbias = _dq_call(tile, q, k, v, do, lse, delta, ex, interpret)
        dk, dv = _dkv_call(tile, q, k, v, do, lse, delta, ex, interpret)
        if tile.has_bias and dbias is None:
            # broadcast pair bias (evoformer: one bias shared by N MSA rows):
            # the reducing kernel keeps the per-replica [B,H,Sq,Skv] tensor
            # out of HBM and returns dbias in the bias's own shape
            dbias = _dbias_call(tile, q, k, v, do, lse, delta, ex, interpret)
        zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        dbias = (dbias.astype(ex["bias"].dtype) if dbias is not None
                 else jnp.zeros_like(ex["bias"]))
        # the k-row (mask) bias is non-differentiable by design — matching
        # the role it plays in the evoformer API (a -inf validity mask)
        return (dq, dk, dv, zero(ex["seg_q"]), zero(ex["seg_k"]),
                zero(ex["pos_q"]), zero(ex["pos_k"]),
                jnp.zeros_like(ex["ab"]), dbias,
                jnp.zeros_like(ex["kbias"]), zero(ex["layout"]))

    f.defvjp(f_fwd, f_bwd)
    return f


# ---------------------------------------------------------------- block sizes
# What a caller that passes no blocks gets, from the sweep on the v5e
# (``tools/tpu_tune.py flash --steps``; PERF.md section 6, PR 47): the block a
# grid step copies and the tile it is walked in. The three kernels asked for
# the same pair at 2,048, 4,096 and 8,192 tokens.
_PREFERRED_BLOCK = (4096, 4096)
_COMPUTE_TILE = 512


def _default_blocks(sq: int, skv: int, plain: bool = True):
    """``(sq_p, skv_p, (block_q, block_k, sub_q, sub_k))`` by ONE rule from
    the shapes: the compute tile is 512 a side (the 128-rounded sequence if
    that is shorter), both sequences pad to it (never more padding than a 512
    block asks), and a grid step takes the largest multiple of the tile up to
    the preferred block that divides the padded length — or, for a call that
    is not ``plain`` (per-key rows or per-pair tiles: :func:`_run_tiles`),
    the tile itself."""
    def fit(prefer, padded, tile):
        return max(m for m in range(tile, max(prefer if plain else tile, tile)
                                    + 1, tile) if padded % m == 0)

    cq = min(_COMPUTE_TILE, _round_up(sq, _LANES))
    ck = min(_COMPUTE_TILE, _round_up(skv, _LANES))
    sq_p, skv_p = _round_up(sq, cq), _round_up(skv, ck)
    return sq_p, skv_p, (fit(_PREFERRED_BLOCK[0], sq_p, cq),
                         fit(_PREFERRED_BLOCK[1], skv_p, ck), cq, ck)


def tile_plan(sq: int, skv: int, block_q: int, block_k: int, causal: bool,
              offset: int, window: Optional[int]) -> dict:
    """How many of a (batch, head)'s ``⌈sq/block_q⌉ x ⌈skv/block_k⌉`` compute
    tiles are ``dead``, ``interior`` and ``boundary`` (``live`` = the last
    two) under default positions — the same arithmetic the kernels run
    (:func:`_static_kind`), without segments or a layout."""
    nq, nkv = -(-sq // block_q), -(-skv // block_k)
    if window is not None and window >= skv:
        window = None
    t = _Tile(1.0, causal, offset, False, False, sq, skv, block_q, block_k,
              nq, nkv, False, window, False, False, False, block_q, block_k)
    plan = dict(live=0, interior=0, boundary=0, dead=0)
    for i in range(nq):
        for j in range(nkv):
            live, interior = _static_kind(t, i, j)
            kind = ("dead" if not live else
                    "interior" if interior else "boundary")
            plan[kind] += 1
    plan["live"] = plan["interior"] + plan["boundary"]
    return plan


@functools.lru_cache(maxsize=None)
def _log_plan(sq, skv, block, causal, offset, window):
    """One line per traced shape, beside the engine's rung line."""
    bq, bk, cq, ck = block
    plan = tile_plan(sq, skv, cq, ck, causal, offset, window)
    logger.info("flash attention Sq %d Skv %d causal %s window %s: a (batch, "
                "head)'s %dx%d tiles, in grid steps of %dx%d: %s", sq, skv,
                causal, window, cq, ck, bq, bk, plan)
    return plan


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
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
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
    ``ops/sparse_attention.py``). ``block_q`` / ``block_k``: the tile of all
    three kernels, copied and computed as one (clamped to the 128-padded
    sequence); left out, :func:`_default_blocks`' (a layout's blocks default
    to 512).
    Returns ``[B,Sq,H,D]`` in q's dtype. Off-TPU runs in interpret mode.

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
    has_seg = segment_ids is not None or kv_segment_ids is not None
    if window is not None and not custom_pos and window >= skv:
        window = None   # q sees at most Skv - 1 positions back: cannot bite

    if block_q is None and block_k is None and block_layout is None:
        sq_p, skv_p, block = _default_blocks(
            sq, skv, plain=not (has_seg or custom_pos or bias is not None
                                or k_bias is not None))
    else:
        # the caller's tile (a block-sparse layout's contract, ring
        # attention's), clamped to the (padded) sequence, for all three
        bq = min(block_q or 512, _round_up(sq, _LANES))
        bk = min(block_k or 512, _round_up(skv, _LANES))
        sq_p, skv_p, block = _round_up(sq, bq), _round_up(skv, bk), \
            (bq, bk, bq, bk)
    d_p = _round_up(d, _LANES)

    def pad(x, s_to, axis_s):
        cfg = [(0, 0)] * 4
        cfg[axis_s] = (0, s_to - x.shape[axis_s])
        cfg[3] = (0, d_p - d)
        return jnp.pad(x, cfg) if any(p != (0, 0) for p in cfg) else x

    qt = pad(jnp.transpose(q, (0, 2, 1, 3)), sq_p, 2)     # [B,H,Sq,D]
    kt = pad(jnp.transpose(k, (0, 2, 1, 3)), skv_p, 2)    # [B,KVH,Skv,D]
    vt = pad(jnp.transpose(v, (0, 2, 1, 3)), skv_p, 2)

    unused = jnp.zeros((1, 1), jnp.int32)   # placeholder no kernel is handed
    seg_q = seg_k = pos_q = pos_k = unused
    if has_seg:
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
        seg_q = jnp.pad(sq_ids, ((0, 0), (0, sq_p - sq)), constant_values=-2)
        seg_k = jnp.pad(sk_ids, ((0, 0), (0, skv_p - skv)),
                        constant_values=-1)
    if custom_pos:
        q_pos = (jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32) + offset,
                                  (b, sq))
                 if q_positions is None else q_positions.astype(jnp.int32))
        kv_pos = (jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32),
                                   (b, skv))
                  if kv_positions is None else kv_positions.astype(jnp.int32))
        # pad kv positions huge so a pad slot is never <= any real q position
        pos_q = jnp.pad(q_pos, ((0, 0), (0, sq_p - sq)))
        pos_k = jnp.pad(kv_pos, ((0, 0), (0, skv_p - skv)),
                        constant_values=2**30)

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
        # the kernels are handed [Bk, 1, Skv] (or [Bk, Skv, 1]): Mosaic
        # requires the second-to-last block dim be 8-divisible or full — a
        # batch window of 1 over Bk>1 is neither, so the batch axis must sit
        # outside the last two dims
        kbias_p = jnp.pad(k_bias, ((0, 0), (0, skv_p - skv)))
    else:
        kbias_p = jnp.zeros((1, 1), jnp.float32)  # unused placeholder
    if block_layout is not None:
        nq_b, nkv_b = sq_p // block[0], skv_p // block[1]
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
    if not custom_pos:
        _log_plan(int(sq), int(skv), block, bool(causal), int(offset),
                  window)
    bq, bk, cq, ck = block
    tile = _Tile(float(1.0 / np.sqrt(d)), bool(causal), int(offset),
                 custom_pos, has_seg, int(sq), int(skv), bq, bk,
                 sq_p // bq, skv_p // bk, alibi is not None,
                 None if window is None else int(window),
                 bias is not None, k_bias is not None,
                 block_layout is not None, cq, ck)
    fn = _make_flash(tile, bool(return_lse), bool(interpret))
    out = fn(qt, kt, vt, seg_q, seg_k, pos_q, pos_k, ab, bias_p, kbias_p,
             layout_a)                                    # [B,H,Sq_p,D_p]
    if return_lse:
        out, lse = out
        out = jnp.transpose(out[:, :, :sq, :d], (0, 2, 1, 3))
        return out, jnp.transpose(lse.reshape(b, h, sq_p)[:, :, :sq],
                                  (0, 2, 1))
    out = out[:, :, :sq, :d]
    return jnp.transpose(out, (0, 2, 1, 3))
