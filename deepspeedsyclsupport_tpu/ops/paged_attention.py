"""Paged (blocked-KV) attention, decode and ragged prefill — ONE Pallas TPU
kernel body at two tile heights.

The performance core of the v2 ragged engine: the reference's
``blocked_flash`` CUDA kernel family (``inference/v2/kernels/ragged_ops/
blocked_flash``, atom-based flash attention over paged KV). A tile of query
rows of ONE sequence attends over that sequence's KV blocks, resolved
through a block table. Which rows take which tile follows the chunk's
length (``inference/v2/ragged.build_ragged_batch``):

* a ONE-token chunk (every row of ``decode_forward``; in a mixed
  ``ragged_forward`` the decoding sequences and a one-token prompt) is a
  tile of one row: :func:`paged_decode_attention`, one grid program per
  sequence slot, the custom call a profile names ``paged_decode``;
* a chunk of two tokens or more is cut into ATOMS of ``atom_q_size`` (128,
  fewer under many heads of one kv head: :func:`default_atom_rows`)
  rows: :func:`ragged_prefill_attention`, one grid program per atom,
  ``ragged_prefill`` in a profile. A 128-row tile costs the MXU and the
  mask/exp over ``[KVH, 128·G, block]`` whether one of its rows is live or
  all, which is why a decode step is never given one.

Kernel shape (TPU-first, not a CUDA translation):

* grid = one program per tile; the block table row, the tile's first
  position and its live rows ride in as SCALAR-PREFETCH args so KV block
  DMAs can be issued immediately (``PrefetchScalarGridSpec`` — the Pallas
  idiom for indirect addressing).
* K/V stay in HBM; each loop iteration (a STEP) DMAs its KV blocks into one
  of two VMEM scratch slots, each block through its own block-table entry,
  and folds them into an online-softmax accumulator (flash recurrence) with
  ONE ``q k^T``, one mask / max / exp / sum, one ``p v`` and one rescale of
  the accumulator, so VMEM holds O(step · D) regardless of context length,
  and compute overlaps the next step's fetch via the DMA queue. A step is
  ONE block under a K-and-V pool's one-row tile and several under every
  other tile, by the tile's shape (:func:`_kv_pages_per_step`).
* the overlap does not stop at a tile's end: a tile's LAST step starts the
  FIRST step's copies of the next tile of the grid (where both walk at
  least one step: :func:`tile_span`), into the scratch slot it does not
  read itself, and that tile only waits for them. So the bus works through
  a tile's last products, its division and write-out and the next tile's
  q turned head-major, where it used to stand idle: only the first tile of
  a call, and one behind a tile that walks nothing, start cold. The two
  slots and their semaphores live across grid steps, so the grid must run
  in order on one core (``dimension_semantics`` ``arbitrary``, stated).
* GQA: queries reshape to [KVH, G, D] and each kv head batch-matmuls its
  group — grouped heads share the streamed KV block, the reason GQA decode is
  bandwidth-cheap on TPU.

Latent (MLA) pools: ``v_cache=None, v_dim=n`` means the pool holds ONE row
a token, ``[L, num_slots, D]`` with no head axis (an axis of one would be
tiled up to two in HBM), and the value is its leading ``n`` lanes (the
normed latent; the row's tail is the rotated key all heads share). The same
body then streams one tile a block instead of two, slices V from the K tile
already in VMEM, returns ``[..., H, n]``, and feeds the MXU the pool's own
dtype: under 32 query heads the absorbed form is ~70 kFLOP a (row, cached
token), compute-bound, where float32 operands cost several passes. Its block
is small (80 KiB at 64 rows x 640 bf16: half the MXU's columns and depth,
one short DMA), so its step takes several: on the v5e a 2,048-row tile pays
5.5 us a block at one a step and 2.4 at four (the rescale of a 4 MiB
accumulator and the [rows, 1] columns of the softmax are paid a step, not a
block), a one-row tile 0.47-0.54 against 0.17-0.18 at eight (PERF.md, PR
38). Decided at trace time. Under a sparse-attention indexer's selection
(``sel``: GLM-5's, over a latent pool) the latent tile takes the step's
``[BQ, keys]`` of the selection with the step's DMAs, as a K-and-V pool's
does, one row's flags for all its heads' lanes, and a pair counts only where
they are set (64 heads x 640 over 32-row atoms, 256 keys a step: PR 65).

K-and-V pools walk ``tile_step`` at either tile height: the mask made once
a step for ONE kv head's rows and added to all as a float32 bias; q, K, V
and ``p`` fed to the MXU in the pool's dtype (at the default precision
Mosaic rounds a float32 operand to bf16 anyway: on the v5e a float32 product
equals the product of the bf16-rounded operands to 7e-6, and the same step
in either dtype is bit for bit the same result and the same time). A tile
of several rows (a prompt's atoms) takes 128-512 keys a step by the rule
the latent pool has, a one-row tile one block. 32 heads over 4 kv heads
under a selection at 32 k: 2.94 us a 64-key block of a 128-row atom at 128
keys a step in the body PR 45 left, 1.71 at 512, 1.62 here; taking the kv
heads one at a time in a loop (so that only one head's scores stand in
VMEM) read 2.39, and reading a head's K and V from the scratch by a
sublane-strided slice 4.49; the one-row tile reads 2-7 % under the float32
step it had at 8 to 32 kv heads and 4 % over at 2 (PERF.md, PR 46).

An exact jnp reference (:func:`paged_decode_attention_reference`) serves
off-TPU fallback and the kernel-vs-reference parity tests (the pattern the
reference repo uses for every CUDA kernel, SURVEY.md §4).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Mosaic's default scoped-VMEM limit; the ragged kernel states its own need
# above it (see _ragged_vmem_limit) instead of shrinking the atom
_DEFAULT_SCOPED_VMEM = 16 << 20
# stay under the 128 MiB of physical VMEM a v4/v5e/v6e core has
_VMEM_CAP = 100 << 20


# --------------------------------------------------------------------- kernel
def paged_decode_attention_pallas(q, k_cache, v_cache, block_tables, seq_lens,
                                  *, block_size: int, layer=0,
                                  alibi=None, window=None, v_dim=None,
                                  interpret: bool = False,
                                  name: str = "paged_decode"):
    """q: [S, H, D]; k/v_cache: [num_slots, KVH, D], or the whole pool
    [L, num_slots, KVH, D] with ``layer`` naming the one to read;
    block_tables: [S, Bps]; seq_lens: [S] valid KV tokens per slot.
    ``alibi``: per-head slopes [H]; ``window``: sliding-window bound;
    ``name``: what a profile calls the kernel. Returns [S, H, D].

    Decode IS the single-row case of the generalized ragged kernel below
    (the paper's prefill/decode unification): each slot becomes a BQ=1 atom
    whose query position is its newest cached token — one kernel family to
    maintain, one DMA/online-softmax pipeline to tune."""
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    pos0 = jnp.maximum(seq_lens - 1, 0)
    qlen = jnp.where(seq_lens > 0, 1, 0).astype(jnp.int32)
    out = ragged_prefill_attention_pallas(
        q[:, None], k_cache, v_cache, block_tables, pos0, qlen,
        block_size=block_size, layer=layer, alibi=alibi, window=window,
        v_dim=v_dim, interpret=interpret, name=name)
    return out[:, 0]


# ------------------------------------------------------------------ reference
def paged_decode_attention_reference(q, k_cache, v_cache, block_tables,
                                     seq_lens, *, block_size: int, layer=0,
                                     alibi=None, window=None, v_dim=None):
    """Exact jnp oracle — decode as the BQ=1 case of the ragged reference
    (one oracle to maintain, mirroring the Pallas unification)."""
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    out = ragged_prefill_attention_reference(
        q[:, None], k_cache, v_cache, block_tables,
        jnp.maximum(seq_lens - 1, 0), (seq_lens > 0).astype(jnp.int32),
        block_size=block_size, layer=layer, alibi=alibi, window=window,
        v_dim=v_dim)
    return out[:, 0]


def _resolve_impl(impl: str, what: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(f"unknown {what} attention impl {impl!r} "
                         f"(auto | pallas | pallas_interpret | xla)")
    return impl


def paged_decode_attention(q, k_cache, v_cache, block_tables, seq_lens, *,
                           block_size: int, impl: str = "auto", layer=0,
                           alibi=None, window=None, v_dim=None,
                           name: str = "paged_decode"):
    """Dispatch (the op-binding seam, like ``models/layers.attention``)."""
    impl = _resolve_impl(impl, "paged decode")
    kw = dict(block_size=block_size, layer=layer, alibi=alibi, window=window,
              v_dim=v_dim)
    if impl == "xla":
        return paged_decode_attention_reference(
            q, k_cache, v_cache, block_tables, seq_lens, **kw)
    return paged_decode_attention_pallas(
        q, k_cache, v_cache, block_tables, seq_lens,
        interpret=impl == "pallas_interpret", name=name, **kw)


# ===================================================================== prefill
def tile_span(pos0, qlen, *, block_size: int, max_blocks: int, pages: int = 1,
              window=None, latent: bool = False, xp=jnp):
    """``(lo_blk, lo_step, kv_hi, walks)`` of a tile from its two scalars:
    the first block and the first loop step its window leaves it, the keys
    it may see (clamped to its table), and whether its loop runs AT ALL.
    ``walks`` is the one predicate of the hand-over between tiles: a tile
    starts its successor's first copies iff both walk, and waits for copies
    it did not start iff it and its predecessor walk; the kernel evaluates
    it on its own scalars and on both neighbours' (``xp`` ``jnp``), a test
    or the host on a whole batch (``xp=np``). A K-and-V pool's tile with no
    row never enters the body; a latent pool's does, and walks what
    ``pos0`` alone leaves it (nothing, as the engine builds a dead tile)."""
    kv_hi = xp.minimum(pos0 + qlen, max_blocks * block_size)
    # sliding window: blocks entirely below row 0's window are masked for
    # EVERY row — skip their DMA and matmuls instead of NEG_INF-ing them
    lo_blk = 0 * pos0 if window is None else \
        xp.maximum(pos0 + 1 - window, 0) // block_size
    lo_step = lo_blk if pages == 1 else lo_blk // pages
    # on lo_step (not just kv_hi > 0): with a sliding window and pos0 beyond
    # the table's capacity, lo_blk can reach max_blocks — the loop would run
    # zero iterations, so a copy started for it would index the table out of
    # bounds and never be awaited
    walks = lo_step * (pages * block_size) < kv_hi
    if not latent:
        walks = xp.logical_and(walks, qlen > 0)
    return lo_blk, lo_step, kv_hi, walks


def warm_tiles(walks) -> int:
    """Tiles of ONE kernel call whose first step the tile before them
    fetched: ``walks`` [tiles] bool in grid order (:func:`tile_span`'s, or
    what a host knows of it: a live row, a live atom). The tiles that walk,
    less one for the first of every unbroken run of them."""
    walks = np.asarray(walks, bool)
    return int(np.count_nonzero(walks[1:] & walks[:-1]))


def _prefill_kernel(block_tables_ref, pos0_ref, qlen_ref, layer_ref,  # scalars
                    q_ref, k_hbm, *refs, v_dim=None, masked: bool = False,
                    **choices):
    """:func:`_attend_tile` for a grid step whose tile is live. A K-and-V
    pool's DEAD tile (no row: the places of a forward's static grid that
    the batch left empty, 33 of 39 atoms in a short mixed round) writes its
    zeros and does nothing else: no q turned head-major, no accumulator, no
    division (on the v5e, 39 atoms of 32 heads of which 4 live at 300 keys:
    0.47 ms a call, 0.82 when the dead walked the body; PERF.md, PR 46). A
    latent pool's every tile walks the body, a dead one through a loop of
    zero steps (it hands over nothing and is handed nothing)."""
    a = pl.program_id(0)
    tile = functools.partial(
        _attend_tile, a, block_tables_ref, pos0_ref, qlen_ref, layer_ref,
        q_ref, k_hbm, *refs, v_dim=v_dim, masked=masked, **choices)
    if v_dim is not None:
        return tile()
    out_ref = refs[3 if masked else 2]
    live = qlen_ref[a] > 0
    pl.when(live)(tile)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)


def _attend_tile(a, block_tables_ref, pos0_ref, qlen_ref, layer_ref,
                 q_ref, k_hbm, *refs,
                 block_size: int, max_blocks: int, group: int,
                 use_alibi: bool, window, v_dim=None, pages: int = 1,
                 masked: bool = False, head_tiles: int = 1):
    """One program per ATOM: a ≤block_q-token slice of ONE sequence's packed
    prefill chunk — or, at ``BQ = 1`` (the decode entry), one sequence's
    newest token; the serving forwards never put a one-token chunk into a
    taller tile. The atom's q tile attends over the sequence's paged KV
    (resolved through its block-table row) with per-row causality — the
    'ragged paged attention' unification of prefill and decode (paper
    arXiv:2604.15464; reference atom_builder + blocked_flash,
    ``inference/v2/kernels/ragged_ops/``). KV blocks stream through the same
    double-buffered DMA pipeline as the decode kernel, so per-sequence KV is
    NEVER materialized in HBM (the O(S·max_ctx) gather this replaces).

    ``refs`` after K: V in HBM, the alibi slopes, the output, the K and V
    scratch, the DMA semaphores and one int32 in SMEM; a latent pool
    (``v_dim``: V is the leading lanes of the K tile) has neither V nor its
    scratch. A second grid axis of ``head_tiles`` steps, where the wrapper
    made one, tiles the heads of the ONE kv head: the body sees its tile's
    heads only and needs no index of it but to name its neighbours.
    ``masked`` (a pool under a sparse-attention indexer): after the pools
    the SELECTION ``[A, BQ, keys]`` int8 in HBM and, after theirs, its
    scratch;
    a step's ``[BQ, step keys]`` of it (whole 128-key lane tiles) rides the
    step's DMAs and a pair counts only where it is nonzero. A selection of
    BLOCKS a kv head (chosen a KV GROUP: ``inference/v2/bsa.py``) arrives
    cut into steps, ``[A, steps, KVH, pages, BQ]``: a step's ``pages`` rows
    of ``BQ`` lanes a head ride its DMAs, are widened to the step's keys
    here (a product with the one-hot ``[pages, step keys]``, which turns
    them row-major on the way), and a kv head's rows count under their own
    head's. No array of keys is made outside the kernel.

    The hand-over: the tile's last step starts the first step's copies of
    its SUCCESSOR in the grid (the next head tile of the atom, else the
    next atom) where that walks a step (:func:`tile_span`, on the
    successor's own scalars), and a tile whose PREDECESSOR walked waits for
    its first step without starting it. Which of the two slots that step
    lies in is carried in the SMEM scalar: the tile that hands over writes
    it (the slot its own last step does not read), so a step's slot follows
    the steps the call has walked and no longer the step's own parity; a
    cold tile starts in slot 0. Every kind of tile takes it, by the one
    rule. On the v5e, us a tile beside its blocks, cold | warm: a one-row
    tile 1.44 | 0.24 at 32 heads over 32, 0.89 | 0.24 at 16 over 16, 0.53 |
    0.19 at 32 over 2, a latent pool's 2.03 | 1.10; a 128-row atom 13.9 |
    12.4 at 16 over 16, 22.8 | 22.4 at 32 over 32, a latent pool's 36.7 |
    29.4, and 13.6 | 13.75 at 32 over 2 and 18.15 | 18.24 under a selection
    at 32 over 4: those two read 0.5-1.2 % MORE (their first copy already
    rode under the atom's own prologue) and keep the rule all the same
    (PERF.md section 6, PR 52)."""
    latent = v_dim is not None
    sel_hbm = sel_vmem = None
    if latent and masked:
        sel_hbm, ab_ref, out_ref, k_vmem, sel_vmem, sem, slot_ref = refs
    elif latent:
        ab_ref, out_ref, k_vmem, sem, slot_ref = refs
    elif masked:
        (v_hbm, sel_hbm, ab_ref, out_ref, k_vmem, v_vmem, sel_vmem,
         sem, slot_ref) = refs
    else:
        v_hbm, ab_ref, out_ref, k_vmem, v_vmem, sem, slot_ref = refs
    # the MXU is fed the pool's own dtype: no float32 copy of q, K or V
    mxu = k_vmem.dtype
    layer = layer_ref[0]   # which [num_slots, KVH, D] of the pool to read
    # the loop below walks STEPS of ``pages`` blocks: one block where the
    # wrapper gave one (a K-and-V pool's one-row tile: the loop over blocks
    # it always was)
    step_keys = pages * block_size
    span = functools.partial(tile_span, block_size=block_size,
                             max_blocks=max_blocks, pages=pages,
                             window=window, latent=latent)
    # kv tokens this atom may see, clamped to the block table's capacity so
    # no copy can index past the table or be started and never awaited
    pos0 = pos0_ref[a]
    qlen = qlen_ref[a]
    lo_blk, lo_step, kv_hi, walks = span(pos0, qlen)
    # the grid's tile before this one and the one after it: the atom's
    # other head tiles first. Their scalars are scalar-prefetch arrays,
    # readable at any index (clamped into the grid; ``has_*`` says whether
    # the neighbour is there)
    atoms = pl.num_programs(0)
    if head_tiles > 1:
        t = pl.program_id(1)
        before = jnp.where(t > 0, a, a - 1)
        after = jnp.where(t + 1 < head_tiles, a, a + 1)
    else:
        before, after = a - 1, a + 1
    has_before, has_after = before >= 0, after < atoms
    before, after = jnp.maximum(before, 0), jnp.minimum(after, atoms - 1)
    warm = jnp.logical_and(
        jnp.logical_and(walks, has_before),
        span(pos0_ref[before], qlen_ref[before])[3])
    next_lo_blk, next_lo_step, next_kv_hi, next_walks = span(
        pos0_ref[after], qlen_ref[after])
    next_walks = jnp.logical_and(next_walks, has_after)
    # a K-and-V pool's q is turned kv head-major as float32, where a kv
    # head's group of 8 is whole vregs (a move, no shuffle)
    q = q_ref[0] if latent else q_ref[0].astype(jnp.float32)   # [BQ, H, D]
    bq, h, d = q.shape
    d_v = v_dim if latent else d
    kvh = 1 if latent else k_vmem.shape[2]
    g = group
    # [KVH, BQ·G, D]: kv head-major so each kv head batch-matmuls its group
    q_g = jnp.transpose(q.reshape(bq, kvh, g, d), (1, 0, 2, 3)) \
        .reshape(kvh, bq * g, d).astype(mxu)
    # q row of each [BQ·G] lane (its position is pos0 + row); a K-and-V
    # pool's mask is one kv head's, which all share
    row = jax.lax.broadcasted_iota(
        jnp.int32, (kvh, bq * g, step_keys) if latent
        else (bq * g, step_keys), 1 if latent else 0) // g

    def copies(step, slot, tile=a, first=lo_blk, hi=kv_hi):
        """The copies of ``step`` of ``tile`` (this one, or the grid's next
        with ITS first block and keys) into ``slot``. A wide step's blocks
        below the window's first or past the context's last are read from
        the nearest block the loop of one block a step would read: every
        key of such a block is masked by its POSITION, but 0 x whatever lay
        in scratch or in a page the sequence does not own is NaN where that
        is NaN."""
        pools = ((k_hbm, k_vmem),) if latent \
            else ((k_hbm, k_vmem), (v_hbm, v_vmem))
        cps = []
        for i in range(pages):       # each block through its own table entry
            blk = block_tables_ref[tile, step if pages == 1 else jnp.clip(
                step * pages + i, first, (hi - 1) // block_size)]
            for n, (hbm, vmem) in enumerate(pools):
                dst = vmem.at[slot] if pages == 1 else \
                    vmem.at[slot, pl.ds(i * block_size, block_size)]
                cps.append(pltpu.make_async_copy(
                    hbm.at[layer, pl.ds(blk * block_size, block_size)],
                    dst, sem.at[slot, n]))
        if masked and sel_vmem.ndim == 3:
            # the step's columns of the atom's selection of keys
            cols = pl.ds(pl.multiple_of(step * step_keys, step_keys),
                         step_keys)
            cps.append(pltpu.make_async_copy(
                sel_hbm.at[tile, :, cols], sel_vmem.at[slot],
                sem.at[slot, len(pools)]))
        elif masked:     # the step's blocks a kv head, whole rows of BQ
            cps.append(pltpu.make_async_copy(
                sel_hbm.at[tile, step], sel_vmem.at[slot],
                sem.at[slot, len(pools)]))
        return cps

    # Every copy is started once and awaited once, on the semaphore of the
    # slot it was started into; the ORDER is the grid's (sequential, one
    # core). Step ``j`` of a tile is awaited at the top of its own loop
    # turn, in ``slot_of(j)``. It was started (1) by the turn before it, of
    # the same tile, into the slot that turn did not read; or, the tile's
    # first step, (2) by this prologue into slot 0, iff the tile walks and
    # is not ``warm``; or (3) by the LAST turn of the grid's tile before,
    # into the slot that turn did not read, written to ``slot_ref``, iff
    # that tile walked and this one walks: the same two facts, from the
    # same scalars through the same function, as ``warm`` here. So exactly
    # one of (2) and (3) holds for a tile that walks and neither for one
    # that does not: a dead or absent successor is handed nothing, and no
    # tile waits for what nobody started. A last turn's hand-over lands in
    # the slot the turn before it read, as its own next step would have.
    first_slot = jnp.where(warm, slot_ref[0], 0)

    def slot_of(j):
        return jax.lax.rem(first_slot + j - lo_step, 2)

    @pl.when(jnp.logical_and(walks, jnp.logical_not(warm)))
    def _():
        for cp in copies(lo_step, first_slot):
            cp.start()

    def start_next(j):
        """In a turn that is not the tile's last, its next step's copies;
        in its last, the first step's of the grid's next tile: one
        conditional start, the scalars chosen."""
        more = jnp.logical_and((j + 1) * step_keys < kv_hi,
                               j + 1 < -(-max_blocks // pages))
        pick = functools.partial(jnp.where, more)

        @pl.when(jnp.logical_or(more, next_walks))
        def _():
            for cp in copies(pick(j + 1, next_lo_step), 1 - slot_of(j),
                             pick(a, after), pick(lo_blk, next_lo_blk),
                             pick(kv_hi, next_kv_hi)):
                cp.start()

    def latent_step(j, carry):
        """A step of a latent pool's tile: rows [keys, D] of ONE kv head,
        the value their leading lanes."""
        m, l, acc = carry
        # read by nothing since the K-and-V step left this body (it was
        # that step's ``active``); it goes with the dead tiles' walk
        # (ROADMAP, Speed 4), not with the hand-over
        j * step_keys < kv_hi  # noqa: B018
        cur = slot_of(j)
        start_next(j)
        for cp in copies(j, cur):
            cp.wait()
        k_t = k_vmem[cur][None]
        v_t = k_t[..., :d_v]
        scores = jax.lax.dot_general(           # [1, BQ·H, keys]
            q_g, k_t, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) / np.sqrt(d)
        pos = j * step_keys + jax.lax.broadcasted_iota(
            jnp.int32, (kvh, bq * g, step_keys), 2)
        if use_alibi:
            scores = scores + ab_ref[...].astype(jnp.float32) * (
                pos - (pos0 + row)).astype(jnp.float32)
        seen = pos0 + row                            # per-row causality
        if pages > 1:
            # a wide step's last blocks lie past kv_hi, where the loop of
            # one block a step never went: a context longer than its table
            # sees what the table holds, under either loop
            seen = jnp.minimum(seen, kv_hi - 1)
        valid = jnp.logical_and(pos <= seen, row < qlen)
        if window is not None:
            valid = jnp.logical_and(valid, (pos0 + row) - pos < window)
        if masked:
            # [BQ, keys] -> a row's H lanes alike -> the scores' [BQ·H, keys]
            chosen = jnp.broadcast_to(
                sel_vmem[cur].astype(jnp.float32)[:, None, :],
                (bq, g, step_keys)).reshape(bq * g, step_keys)
            valid = jnp.logical_and(valid, chosen[None] > 0.0)
        scores = jnp.where(valid, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(mxu), v_t, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    def heads_of(x):
        """A slot's ``[keys, KVH, D]`` head-major, in the pool's dtype. Two
        kv heads of 16 bits share a 32-bit sublane: they are taken apart by
        widening (on the v5e a one-row tile under 2 kv heads reads 0.52 us a
        block that way and 0.55 turned as stored; 4 to 32 kv heads read 2-13
        % less turned as stored)."""
        if kvh * x.dtype.itemsize < 8:
            return jnp.transpose(x.astype(jnp.float32), (1, 0, 2)).astype(mxu)
        return jnp.transpose(x, (1, 0, 2))

    def tile_step(j, carry):
        """A step of a K-and-V pool's tile. What does not depend on the kv
        head is made once, for one head's ``[BQ·G, keys]``, and shared: the
        step's mask as a float32 bias, 0 where the row attends to the key
        and far under ``m``'s first value where not, so a masked pair's
        ``exp`` is 0 with no second select and a row that has seen nothing
        yet keeps ``l`` 0. The MXU takes q as it arrived,
        K and V as stored (turned head-major, no float32 copy) and ``p`` in
        the pool's dtype: the values a float32 operand is rounded to at the
        default precision anyway."""
        m, l, acc = carry
        cur = slot_of(j)
        start_next(j)
        for cp in copies(j, cur):
            cp.wait()
        pos = j * step_keys + jax.lax.broadcasted_iota(
            jnp.int32, (bq * g, step_keys), 1)
        q_pos = pos0 + row
        # a context longer than its table sees what the table holds
        valid = pos <= jnp.minimum(q_pos, kv_hi - 1)
        if window is not None:
            valid = jnp.logical_and(valid, q_pos - pos < window)
        if masked:
            chosen = sel_vmem[cur].astype(jnp.float32)
            if chosen.ndim == 3:
                # blocks a kv head [KVH, pages, BQ] -> [KVH, BQ, keys]: a
                # block's flag at each of its keys (0 or 1: exact)
                block_of = jax.lax.broadcasted_iota(
                    jnp.int32, (pages, step_keys), 1) // block_size
                spread = (block_of == jax.lax.broadcasted_iota(
                    jnp.int32, (pages, step_keys), 0)).astype(jnp.float32)
                chosen = jnp.stack([jax.lax.dot_general(
                    chosen[n], spread, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for n in range(kvh)])
            # [BQ, keys] -> a row's G lanes alike -> the scores' [BQ·G, keys]
            # (a selection a kv head: [KVH, BQ, keys] -> [KVH, BQ·G, keys])
            lead = chosen.shape[:-2]
            chosen = jnp.broadcast_to(
                chosen[..., None, :], (*lead, bq, g, step_keys)).reshape(
                    *lead, bq * g, step_keys)
            valid = jnp.logical_and(valid, chosen > 0.0)
        bias = jnp.where(valid, 0.0, 2 * NEG_INF)
        scores = jax.lax.dot_general(           # [KVH, BQ·G, keys]
            q_g, heads_of(k_vmem[cur]),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * np.float32(1 / np.sqrt(d))
        if use_alibi:
            scores = scores + ab_ref[...].astype(
                jnp.float32) * (pos - q_pos).astype(jnp.float32)
        scores = scores + bias
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        pv = jax.lax.dot_general(
            p.astype(mxu), heads_of(v_vmem[cur]),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    m0 = jnp.full((kvh, bq * g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((kvh, bq * g, 1), jnp.float32)
    acc0 = jnp.zeros((kvh, bq * g, d_v), jnp.float32)
    # DYNAMIC trip count: dead atoms (kv_hi = 0) run zero iterations — with
    # A_max sized for the worst case, most grid programs of a typical batch
    # are dead and must not burn max_blocks MXU loops each
    n_steps = (kv_hi + step_keys - 1) // step_keys
    # where the last turn's hand-over lands, if it hands over (read by a
    # warm successor only)
    slot_ref[0] = 1 - slot_of(n_steps - 1)
    m, l, acc = jax.lax.fori_loop(lo_step, n_steps,
                                  latent_step if latent else tile_step,
                                  (m0, l0, acc0))
    if not latent:
        # tile_step masks a pair by its key, never by the row: what a dead
        # row summed goes here
        acc = jnp.where(jax.lax.broadcasted_iota(
            jnp.int32, (kvh, bq * g, 1), 1) // g < qlen, acc, 0.0)
    out = acc / jnp.maximum(l, 1e-30)
    out = jnp.transpose(out.reshape(kvh, bq, g, d_v), (1, 0, 2, 3))
    out_ref[0] = out.reshape(bq, h, d_v).astype(out_ref.dtype)


# what one grid step may need by _ragged_vmem_need before the heads of a
# single kv head are tiled over a second grid axis: twice it must stay
# under _VMEM_CAP, and 32 heads x d 128 (21 MiB) must stay one tile
_HEAD_TILE_BUDGET = 48 << 20


def _head_tile(bq: int, h: int, kvh: int, d: int, block_size: int,
               itemsize: int) -> int:
    """Query heads one grid step takes, by the SHAPE: all of them, unless
    they share ONE kv head (every tile then reads the same KV block and
    needs nothing of the others) and their tile would pass the budget; then
    the largest halving that fits and stays a multiple of 16 (a bf16 tile's
    sublanes). A 128-row atom of 32 heads x 640 (absorbed latent attention)
    models at 80 MiB and runs as two tiles of 16."""
    ht = h
    while (kvh == 1 and ht % 32 == 0 and _ragged_vmem_need(
            bq, ht, kvh, d, block_size, itemsize) > _HEAD_TILE_BUDGET):
        ht //= 2
    return ht


def default_atom_rows(bq: int, h: int, kvh: int, d: int, block_size: int,
                      itemsize: int) -> int:
    """Rows of an atom nobody chose (``RaggedInferenceConfig.atom_q_size``
    None), by the SHAPE: ``bq`` where the heads of a single kv head take at
    most two grid steps at that height (:func:`_head_tile`); where they
    would take more, the tallest halving at which ONE step takes them all,
    and never under 16 rows (a bf16 tile's sublanes). A grid step's tile,
    rows x heads, stays what the budget allows; every head tile reads the
    same KV blocks again and moves its slice of q and of the result by
    strided DMA, and the forward gathers q into ``[atoms, rows, H, d]`` for
    ``max_sequences`` atoms whether live or not. On the v5e, 128 heads x
    640 over a 768-row chunk: 9.17 ms a layer at 128 rows (eight tiles of
    16 heads, a gather of 1.49 GB at 64 sequences), 6.30 at 32 (two of 64,
    0.47 GB), 5.83 at 16 (one, 0.30 GB). 32 heads x 640 keep the 128 rows
    and two tiles of 16 they were accepted with. Under several kv heads:
    the tallest halving whose ONE step of all heads stays in the budget."""
    def tiles(rows):
        return h // _head_tile(rows, h, kvh, d, block_size, itemsize)

    if kvh > 1:
        # heads of SEVERAL kv heads are never tiled (each tile would read
        # the kv heads it has no use for): where one grid step of them all
        # passes the budget, 128 heads over 8 x 128 at 128 rows (77 MiB
        # modelled), the atom is halved until it fits: 64 rows there
        while bq >= 32 and bq % 2 == 0 and _ragged_vmem_need(
                bq, h, kvh, d, block_size, itemsize) > _HEAD_TILE_BUDGET:
            bq //= 2
        return bq
    if tiles(bq) <= 2:
        return bq
    while bq >= 32 and bq % 2 == 0 and tiles(bq) > 1:
        bq //= 2
    return bq


# the float32 scores a loop step multiplies out at once (one kv head's rows x
# pages x block_size) may take this much, its blocks of K and V this much in
# the pool's dtype, and a step at most this many blocks (_kv_pages_per_step)
_STEP_SCORE_BYTES = 2 << 20
_STEP_KV_BYTES = 2 << 20
_MAX_STEP_PAGES = 8


def _kv_pages_per_step(bq: int, ht: int, kvh: int, d: int, block_size: int,
                       itemsize: int, latent: bool) -> int:
    """KV blocks one step of :func:`_prefill_kernel`'s loop takes, by the
    SHAPE of the tile :func:`_head_tile` and :func:`default_atom_rows` chose
    at one block a step: chosen after them, from the room they left.

    A K-and-V pool's one-row tile: one. Its contexts are 2-40 blocks in
    every cell that has one, and on a latent pool a one-row step too wide
    for its context lost 12-14 % (PR 38): ROADMAP, Speed 1.

    Every other tile pays a STEP, and not a key, for the rescale of its whole
    accumulator, for the ``[rows, 1]`` columns of the softmax and for the two
    cross-lane reductions of its scores, and one 64-key block a step leaves
    half the MXU's columns empty. So the most blocks, a power of two up to
    ``_MAX_STEP_PAGES``, at which one kv head's scores stay under
    ``_STEP_SCORE_BYTES`` (a latent pool has one), the step's K and V under
    ``_STEP_KV_BYTES`` (the first step's fetch hides behind nothing, and a
    context's last step is computed whole: 32 kv heads x 128 move 1 MiB a
    block and their contexts are short) and the model of VMEM under
    ``_VMEM_CAP``. On the v5e, us a 64-key block of a 128-row atom at 1 / 2 /
    4 / 8 blocks a step: 32 heads over 4 kv heads under a selection at 32 k
    - / 2.74 / 1.96 / 1.65 (eight), over 8 at 2 k 5.4 / 3.1 / 2.0 / 1.75
    (eight), 16 over 16 at 1 k 3.6 / 2.5 / 2.1 / 2.1 (four), 32 over 32 at
    1 k 7.2 / 4.8 / 4.0 / 4.1 and at 256 keys 11.2 / 8.8 / 8.5 / 11.8 (two)
    (PERF.md, PR 46); a latent pool's, PR 38."""
    if not latent and bq == 1:
        return 1
    kv_block = block_size * kvh * d * itemsize * (1 if latent else 2)
    pages = _MAX_STEP_PAGES
    while pages > 1 and (
            bq * (ht // kvh) * pages * block_size * 4 > _STEP_SCORE_BYTES
            or pages * kv_block > _STEP_KV_BYTES
            or _ragged_vmem_need(bq, ht, kvh, d, block_size, itemsize,
                                 pages, not latent) > _VMEM_CAP):
        pages //= 2
    return pages


def _selection_pages(pages: int, block_size: int) -> int:
    """``pages`` blocks a step under a sparse-attention indexer's
    selection: the step's slice of the selection is cut along the
    LANES of its [BQ, keys] rows, so a step is whole lane tiles of 128
    keys."""
    return max(pages, min(_MAX_STEP_PAGES, 128 // block_size))


def kv_step_keys(bq: int, h: int, kvh: int, d: int, block_size: int,
                 itemsize: int, latent: bool, masked: bool = False) -> int:
    """Keys one loop step of the kernel covers for a tile of ``bq`` rows
    under ``h`` query heads, as the wrapper below decides it: the head tile
    first, then the blocks a step (``masked``: under an indexer's
    selection). What the engine counts a forward's steps by
    (``ragged.attention_work``)."""
    ht = _head_tile(bq, h, kvh, d, block_size, itemsize)
    pages = _kv_pages_per_step(bq, ht, kvh, d, block_size, itemsize, latent)
    if masked:
        pages = _selection_pages(pages, block_size)
    return block_size * pages


def _ragged_vmem_need(bq: int, h: int, kvh: int, d: int, block_size: int,
                      itemsize: int, pages: int = 1,
                      kv_tile: bool = False, sel_heads: int = 1) -> int:
    """Bytes of VMEM one grid step of :func:`_prefill_kernel` needs, by the
    shape model :func:`_ragged_vmem_limit` explains; a loop step of
    ``pages`` KV blocks holds that many in each scratch slot and scores
    that many times the keys. ``kv_tile``: a K-and-V pool's tile, which
    feeds the MXU the pool's dtype (no float32 K and V) and makes its mask
    once for all kv heads, but may carry a selection's slots; ``sel_heads``:
    the kv heads that selection has (1: one of keys for all; more: one of
    blocks a head), whose mask is then a head's own."""
    q_tile = bq * h * d
    kv = pages * block_size * kvh * d
    scores = bq * h * pages * block_size
    need = (4 * q_tile * itemsize        # q + out tiles, double-buffered
            + 5 * q_tile * 4)            # fp32 q, q_g, acc, acc_new, pv
    if kv_tile:
        return (need
                # k/v scratch, two slots each, the kv heads on sublanes
                # (fewer than a vreg's 32 bytes of them are tiled up to it)
                + 4 * kv * itemsize * max(1, 32 // (kvh * itemsize))
                + 4 * kv * itemsize      # k, v head-major, and in passing
                # the selection's slots: a step's keys, or its blocks a head
                + 2 * bq * pages * (block_size if sel_heads == 1
                                    else sel_heads)
                # pos, q_pos, valid, bias (the last two a selection's head)
                + (2 + 2 * sel_heads) * (scores // kvh) * 4
                + 4 * scores * 4)                  # scores, p twice, exp
    return (need
            + 4 * kv * itemsize          # k/v scratch, two slots each
            + 4 * kv * 4                 # fp32 k, v and their transposes
            + 6 * scores * 4)            # scores, pos, valid, p, exp temps


def _ragged_vmem_limit(bq: int, h: int, kvh: int, d: int, block_size: int,
                       itemsize: int, pages: int = 1,
                       kv_tile: bool = False, sel_heads: int = 1) -> int:
    """Scoped-VMEM limit stated to the compiler for one grid step of
    :func:`_prefill_kernel`. The q/out tiles are double-buffered by the
    pipeline and the body keeps fp32 copies of q, the accumulator and its
    update, so a 128-row atom at 32 heads x d 128 needs 21-22 MiB (bisected
    against the v5e compiler) — over Mosaic's 16 MiB default, which refused
    the kernel at every real width. The shape model below came within
    0.8-1.06x of the bisected need across atoms 64-256 and KVH 4-32 at one
    block a step; the limit is only a ceiling, so twice the model is stated
    (up to the cap). ``h`` is the heads of ONE grid step
    (:func:`_head_tile`), ``pages`` the blocks of one loop step
    (:func:`_kv_pages_per_step`): the model's KV scratch and its six arrays
    of scores grow with them (a latent pool's 2,048-row tile: 38.9 MiB at
    one block, 50.8 at four; the model still counts the float32 copy of q
    and float32 copies of K and V, which the latent branch does not make:
    room, not need). ``kv_tile``: a K-and-V pool's tile, whose step makes no
    float32 K and V and ONE kv head's mask, holds K and V head-major in the
    pool's dtype and, under a selection, its two slots (32 heads over 4 kv
    heads at 512 keys a step: 64 MiB modelled, compiled under the cap it
    states)."""
    need = _ragged_vmem_need(bq, h, kvh, d, block_size, itemsize, pages,
                             kv_tile, sel_heads)
    if need > _VMEM_CAP:
        raise ValueError(
            f"ragged prefill atom of {bq} rows x {h} heads x d {d} needs "
            f"~{need >> 20} MiB of VMEM (cap {_VMEM_CAP >> 20} MiB); lower "
            f"RaggedInferenceConfig.atom_q_size")
    return min(max(2 * need, _DEFAULT_SCOPED_VMEM), _VMEM_CAP)


def ragged_prefill_attention_pallas(q_atoms, k_cache, v_cache, atom_tables,
                                    atom_pos0, atom_qlen, *,
                                    block_size: int, layer=0, alibi=None,
                                    window=None, v_dim=None,
                                    interpret: bool = False,
                                    name: str = "ragged_prefill", sel=None):
    """q_atoms: [A, BQ, H, D] (one sequence per atom row block);
    k/v_cache: the whole pool [L, num_slots, KVH, D], read at ``layer``
    (a traced scalar inside the serving forwards' layer loop: the pool
    stays in HBM and is never sliced by layer outside the kernel), or one
    layer's [num_slots, KVH, D] — the L = 1, layer 0 case of the same body;
    atom_tables: [A, Bps] (the owning sequence's block-table row per atom);
    atom_pos0/atom_qlen: [A]. ``alibi``: per-head slopes [H]; ``window``:
    sliding-window bound. ``name`` is what a profile calls the kernel: its
    custom call's instruction and scope (the decode entry passes its own).
    ``v_cache=None, v_dim=n``: a latent pool, V the leading ``n`` lanes of
    K's rows (the module's docstring). ``sel`` [A, BQ, keys] int8 (either
    kind of pool): a sparse-attention indexer's selection of TOKENS, nonzero
    where the atom's row attends to the position; the kernel then walks the
    steps its tile's shape gives (whole 128-key lane tiles of the selection:
    :func:`_selection_pages`) and a profile calls it ``dsa_prefill``. ``sel``
    [A, KVH, blocks, BQ] int8 (a K-and-V pool only): a selection of BLOCKS a
    KV head, nonzero where the row attends to the table's block, each head's
    rows under their own; the same steps, and the kernel widens a step's
    blocks to its keys in VMEM.
    Returns [A, BQ, H, D] ([.., n])."""
    a, bq, h, d = q_atoms.shape
    latent = v_cache is None
    if sel is not None and latent and sel.ndim != 3:
        raise ValueError("a latent pool has one row for all heads: a "
                         "selection a KV head over it is not written")
    if latent and not v_dim:
        raise ValueError("a pool without V needs v_dim, the lanes of K's "
                         "rows that are the value")
    pools = (k_cache,) if latent else (k_cache, v_cache)
    if k_cache.ndim == (2 if latent else 3):     # one layer's cache
        pools, layer = tuple(pool[None] for pool in pools), 0
    kvh = 1 if latent else k_cache.shape[-2]
    itemsize = q_atoms.dtype.itemsize
    # heads of one grid step; > 1 tiles only under a single kv head
    ht = _head_tile(bq, h, kvh, d, block_size, itemsize)
    tiles = h // ht
    g = ht // kvh
    # KV blocks a loop step takes: chosen AFTER the tile, from what it left
    pages = _kv_pages_per_step(bq, ht, kvh, d, block_size, itemsize, latent)
    sel_heads = 1
    if sel is not None:
        pages = _selection_pages(pages, block_size)
        steps = -(-atom_tables.shape[1] // pages)
        name = "dsa_prefill" if name == "ragged_prefill" else name
        if sel.ndim == 3:
            # whole steps of columns: the last step's DMA reads its full
            # width
            keys = steps * pages * block_size
            sel = jnp.pad(sel[..., :keys].astype(jnp.int8), (
                (0, 0), (0, 0), (0, max(0, keys - sel.shape[-1]))))
        else:
            # whole steps of blocks, a step's [KVH, pages, BQ] contiguous
            sel_heads, blocks = kvh, steps * pages
            sel = jnp.pad(sel[:, :, :blocks].astype(jnp.int8), (
                (0, 0), (0, 0), (0, max(0, blocks - sel.shape[2])), (0, 0)))
            sel = jnp.swapaxes(sel.reshape(a, kvh, steps, pages, bq), 1, 2)
    if alibi is not None:
        # per-lane slope layout matches the kernel's [KVH, BQ·G] score rows:
        # lane (r·G + gi) of kv head kh carries q head kh·G + gi (under one
        # kv head, head tile j's rows follow tile j - 1's)
        ab = jnp.tile(
            jnp.asarray(alibi, jnp.float32).reshape(kvh * tiles, 1, g),
            (1, bq, 1)).reshape(kvh * tiles, bq * g, 1)
    else:
        ab = jnp.zeros((kvh * tiles, bq * g, 1), jnp.float32)

    return _tiled_call(
        jnp.asarray(atom_tables, jnp.int32), jnp.asarray(atom_pos0, jnp.int32),
        jnp.asarray(atom_qlen, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), q_atoms, pools, ab, sel,
        block_size=block_size, ht=ht, pages=pages,
        use_alibi=alibi is not None,
        window=None if window is None else int(window),
        v_dim=v_dim if latent else None,
        vmem_limit=_ragged_vmem_limit(
            bq, ht, kvh, d, block_size, itemsize, pages, not latent,
            sel_heads),
        interpret=interpret, name=name)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "block_size", "ht", "pages", "use_alibi", "window", "v_dim", "vmem_limit",
    "interpret", "name"))
def _tiled_call(atom_tables, atom_pos0, atom_qlen, layer, q_atoms, pools, ab,
                sel=None, *, block_size, ht, pages, use_alibi, window, v_dim,
                vmem_limit, interpret, name):
    """The ``pallas_call`` of :func:`ragged_prefill_attention_pallas`, every
    choice made (``ht`` heads a grid step, ``pages`` KV blocks a loop step).
    Inlined into its caller's trace, so the program is the one a plain call
    gives; but the trace of the kernel body is kept by shapes and choices,
    and a forward calls the one body for each of its layer stacks at both
    tile heights, ``decode_forward`` again at the one-row tile, and every
    further static shape of ``ragged_forward`` at that tile too: tracing the
    body is most of what tracing a serving forward costs."""
    a, bq, h, d = q_atoms.shape
    latent = len(pools) == 1
    kvh = 1 if latent else pools[0].shape[-2]
    row = pools[0].shape[2:]                     # (KVH, D), latent: (D,)
    d_out = v_dim if latent else d
    tiles = h // ht
    g = ht // kvh
    masks = () if sel is None else (sel,)     # in HBM, after the pools

    def tile_of(grid_idx):      # (atom[, head tile]) of a grid step
        return grid_idx[0], (grid_idx[1] if tiles > 1 else 0)

    def qo_map(*idx):
        i, j = tile_of(idx)
        return i, 0, j, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(a, tiles) if tiles > 1 else (a,),
        in_specs=[
            pl.BlockSpec((1, bq, ht, d), qo_map, memory_space=pltpu.VMEM),
            # K (and V) stay in HBM
            *(pl.BlockSpec(memory_space=pl.ANY) for _ in pools + masks),
            pl.BlockSpec((kvh, bq * g, 1),
                         lambda *idx: (tile_of(idx)[1], 0, 0),
                         memory_space=pltpu.VMEM),  # slopes per lane
        ],
        out_specs=pl.BlockSpec((1, bq, ht, d_out), qo_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            *(pltpu.VMEM((2, pages * block_size, *row), pool.dtype)
              for pool in pools),
            # a step of the selection: [BQ, step keys] of a tile's keys,
            # [KVH, pages, BQ] of a kv head's blocks
            *(pltpu.VMEM((2, bq, pages * block_size) if m.ndim == 3
                         else (2, *m.shape[2:]), m.dtype) for m in masks),
            pltpu.SemaphoreType.DMA((2, len(pools) + len(masks))),
            # the slot a handed-over first step lies in (_attend_tile)
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, block_size=block_size,
                               max_blocks=atom_tables.shape[1], group=g,
                               use_alibi=use_alibi, window=window,
                               v_dim=v_dim, pages=pages, masked=bool(masks),
                               head_tiles=tiles)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((a, bq, h, d_out), q_atoms.dtype),
        grid_spec=grid_spec,
        # in order, on one core: a tile's last step starts the next tile's
        # first copies into scratch both see (_attend_tile)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * (2 if tiles > 1 else 1),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=name,
    )(atom_tables, atom_pos0, atom_qlen, layer, q_atoms, *pools, *masks, ab)


def ragged_prefill_attention_reference(q_atoms, k_cache, v_cache, atom_tables,
                                       atom_pos0, atom_qlen, *,
                                       block_size: int, layer=0, alibi=None,
                                       window=None, v_dim=None, sel=None):
    """Exact jnp oracle for the prefill kernel (parity tests + off-TPU).
    Given the whole pool it slices ``pool[layer]`` here, at its seam; given
    no V it takes the leading ``v_dim`` lanes of K's rows."""
    a, bq, h, d = q_atoms.shape
    if v_cache is None:                # latent rows: the one kv head's
        k_cache = k_cache[..., None, :]
    if k_cache.ndim == 4:
        k_cache = k_cache[layer]
        v_cache = None if v_cache is None else v_cache[layer]
    kvh = k_cache.shape[1]
    bps = atom_tables.shape[1]
    max_ctx = bps * block_size
    j = jnp.arange(max_ctx)
    slot = atom_tables[:, j // block_size] * block_size + j % block_size
    k_seq = k_cache[slot].astype(jnp.float32)   # [A, C, KVH, D]
    v_seq = k_seq[..., :v_dim] if v_cache is None \
        else v_cache[slot].astype(jnp.float32)
    if kvh != h:
        rep = h // kvh
        k_seq = jnp.repeat(k_seq, rep, axis=2)
        v_seq = jnp.repeat(v_seq, rep, axis=2)
    logits = jnp.einsum("aqhd,achd->ahqc", q_atoms.astype(jnp.float32),
                        k_seq) / np.sqrt(d)
    r = jnp.arange(bq)[None, None, :, None]
    q_pos = atom_pos0[:, None, None, None] + r
    if alibi is not None:
        logits = logits + jnp.asarray(alibi, jnp.float32)[None, :, None,
                                                          None] * (
            j[None, None, None, :] - q_pos).astype(jnp.float32)
    mask = jnp.logical_and(
        j[None, None, None, :] <= q_pos,
        r < atom_qlen[:, None, None, None])
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - j[None, None, None, :] < window)
    if sel is not None and sel.ndim == 3:        # tokens a tile
        mask = jnp.logical_and(mask, (sel[..., :max_ctx] != 0)[:, None])
    elif sel is not None:       # blocks a kv head [A, KVH, blocks, BQ]
        chosen = jnp.repeat(jnp.swapaxes(sel[:, :, :bps] != 0, 2, 3),
                            block_size, axis=-1)
        mask = jnp.logical_and(mask, jnp.repeat(chosen, h // kvh, axis=1))
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)  # dead rows → 0
    out = jnp.einsum("ahqc,achd->aqhd", p, v_seq)
    return out.astype(q_atoms.dtype)


def ragged_prefill_attention(q_atoms, k_cache, v_cache, atom_tables,
                             atom_pos0, atom_qlen, *, block_size: int,
                             impl: str = "auto", layer=0, alibi=None,
                             window=None, v_dim=None, sel=None,
                             name: str = "ragged_prefill"):
    impl = _resolve_impl(impl, "ragged prefill")
    kw = dict(block_size=block_size, layer=layer, alibi=alibi, window=window,
              v_dim=v_dim, sel=sel)
    if impl == "xla":
        return ragged_prefill_attention_reference(
            q_atoms, k_cache, v_cache, atom_tables, atom_pos0, atom_qlen,
            **kw)
    return ragged_prefill_attention_pallas(
        q_atoms, k_cache, v_cache, atom_tables, atom_pos0, atom_qlen,
        interpret=impl == "pallas_interpret", name=name, **kw)
