"""Mamba-2 (SSD, arXiv:2405.21060) for the serving path: the recurrence of one
mixer over a continuous batch, against a state that lives per sequence SLOT.

``h`` heads of ``p`` channels, ``g`` groups of state ``n`` (head ``i`` reads
group ``i // (h / g)``). Per token, after the depthwise causal convolution
over the sequence's own ``xBC`` (kernel ``k``, zeros before its first token):

    dt  = softplus(dt + dt_bias)             A = -exp(A_log)         [h], f32
    S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t                          [h, p, n]
    y_t = S_t C_t + D x_t

The state pool (``inference/v2/kv_cache.BlockedKV.ssm`` / ``.conv``) holds, per Mamba layer
and slot, ``S`` as ``[g, n, (h / g) x p]`` (float32: the state's width on
the lanes, ``n`` on the sublanes, so that the update is elementwise on whole
registers and ``S C`` sums over sublanes) and the convolution's tail, the
last ``k - 1`` rows of ``xBC``, as ``[k - 1, slots, channels]`` (the slots on
the sublanes: with the three taps there the compiler kept two layouts of
the pool and copied it whole between them, four times a forward; as it is,
a slot's row shares its (8, 128)(2, 1) tile with 15 other slots', which is
why no copy engine moves one row of it). Slot ``S`` (the last) is the sink
padding writes to. A piece whose first position is 0 starts from zeros,
whatever its slot held: the host resets nothing.

Who moves the tail: the ONE-TOKEN rows' kernel (:func:`conv_step`,
``conv_tail_step`` on the TPU: whole blocks of slots x channels in and the
same blocks out, the pool aliased, each slot's row found by an index in
SMEM; gather, convolve, scatter elsewhere) and the PIECES' kernel
(:func:`conv_pieces`, ``conv_pieces`` on the TPU: ONE call a layer over all
the live pieces, before the tail's kernel in a mixed forward and on other
slots; a piece's slot comes and goes in the 16-slot block of the pool that
holds it, by the kernel's own copies, so that the compiler lays the pool
out no other way; elsewhere a loop of :func:`conv_piece` in XLA).
Mamba-2's mixed path keeps the convolution inside its own loop of XLA
(:func:`conv_piece` under :func:`chunked_scan`: hoisted into the kernel it
read no faster in ``nemo3-reason-sat`` and cost a quarter more set-up, PERF.md
section 6, PR 61). Mamba-2's ``xBC`` and the delta rule's q | k | v
(``ops/kda.py``) share :func:`conv_step` and :func:`conv_piece`.

Lightning linear attention (arXiv:2401.04658; minicpm_sala's ``L`` layers)
is the same recurrence with a group a head, a CONSTANT decay a head
(:func:`lightning_decay`), ``dt`` 1, ``D`` 0 and no convolution: ``S_t =
lambda_h S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t`` with B = k, C = q and x =
v. Its two entries, :func:`lightning_step` and :func:`lightning_pieces`, are
the state step and the piece below as they are, against a pool ``[layers,
slots + 1, heads, dim, dim]`` (``BlockedKV.la_s``), under the scopes
``la_step`` and ``la_chunk``; they touch no tail.

Two entries, as the attention kernels have two tiles:

* :func:`decode_step` — ONE token for each of ``[rows]`` slots: shift the
  tail (:func:`conv_step`), update the state IN PLACE. On the TPU a Pallas
  kernel whose state block is the pool's own ``[layer, slot]``
  (scalar-prefetch indices, the pool aliased to the output): each state is
  read once and written once, 2 x 2 MiB a row and layer at Nemotron-3-Nano's
  sizes, which is the step's whole cost. ``xla``: gather, update, scatter
  (the CPU tests' reference, and what the kernels are held against).
* :func:`chunked_scan` — the pieces of the chunks of two tokens or more in
  one flat batch (``ragged.ssm_pieces``: single-sequence runs of at most
  ``chunk`` rows, as the attention's atoms): inside a piece the quadratic
  form, the state passed from piece to piece of one sequence THROUGH ITS
  SLOT (the pieces run in order, under one loop whose trip count is the
  live pieces), the first seeded from the slot or from zeros, the last left
  there. Its recurrence carries the scope ``ssm_chunk`` inside ``ssm_scan``.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor.mfu import scope


def default_impl() -> str:
    """``pallas`` on the TPU, ``xla`` elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most`` (1 at least)."""
    return max(t for t in range(1, n + 1) if n % t == 0 and t <= max(most, 1))


def _conv_weights(p):
    return (p["conv_w"].astype(jnp.float32),
            p["conv_b"].astype(jnp.float32))


# ------------------------------------------------- the depthwise convolution
# ONE causal depthwise convolution with its tail in the pool, for every
# mixer that has one: Mamba-2's ``xBC`` here and the delta rule's q | k | v
# (``ops/kda.py``), which has no bias (``bias`` None). ``w`` [kernel,
# channels] float32; ``conv`` the pool ``[layers, kernel - 1, slots + 1,
# channels]``; the caller names the scope.
def _conv_step_xla(x, w, bias, conv, layer, slots, keep):
    """Gather the tail's rows, convolve, scatter them back one on."""
    k = w.shape[0]
    # the window's rows, oldest first: the tail's k - 1, then the token
    win = [jnp.where(keep[:, None], conv[layer, j, slots], 0)
           for j in range(k - 1)] + [x.astype(conv.dtype)]
    acc = sum(w[j] * win[j].astype(jnp.float32) for j in range(k))
    out = jax.nn.silu(acc if bias is None else bias + acc)
    for j in range(k - 1):
        conv = conv.at[layer, j, slots].set(win[j + 1])
    return out, conv


# The tail's kernel walks the POOL, not the rows: Mosaic copies no single
# row of a tiled array (a slice of the second-minor axis must be whole
# (8, 128)(2, 1) tiles: 8 float32 slots, 16 bfloat16 ones, two to a word), so
# a grid step takes CONV_SLOTS consecutive slots x a tile of channels of the
# layer's k - 1 taps as ONE aligned block in and the same block out, and
# finds each slot's row (the token, the result) by a row index in SMEM. With
# every slot live, as a saturated engine's are, that is the bytes the rows'
# own tails are; with few live it is at most the layer's pool once in and
# once out. The token's and the result's blocks hold ALL of the rows of a
# channel tile for all of its steps: the tile is the most channels whose
# token block stays under CONV_TILE_BYTES (8,192 of Solar's 24,576 at 256
# rows, all of Nemotron's 6,144 at 128). By two sweeps on the v5e
# (tools/tpu_tune.py conv; PERF.md section 6, PR 56; ms a layer at Solar's
# shape): 16 | 32 | 64 | 128 slots at 4,096 channels 0.176 | 0.179 | 0.199 |
# 0.237; 1,024 | 2,048 | 4,096 | 8,192 channels at 16 slots 0.363 | 0.237 |
# 0.176 | 0.152 (a step's fixed cost against its bytes), all of the channels
# 0.171 (the first step waits for 25 MB of tokens); the two loops over a
# step's slots unrolled 0.142 against 0.152 rolled.
CONV_SLOTS = 16
CONV_TILE_BYTES = 8 << 20


def _conv_tail_kernel(layer_ref, row_ref, keep_ref, *refs, taps, bias):
    """Grid step ``(c, g)``: slots ``g x S .. (g + 1) x S`` of channel tile
    ``c``. ``row_ref`` [slots] the row that stands at a slot (-1: none),
    ``keep_ref`` [rows]; ``x_ref`` / ``out_ref`` [rows, lanes] float32 (all
    of the rows, held for every ``g``), ``tail_ref`` / ``new_ref`` [taps -
    1, S, lanes] the pool's block in and out, ``xs_ref`` / ``ys_ref`` [S,
    lanes] float32 the tokens and the results in the slots' order,
    ``code_ref`` [S, 128] a slot's 0 (no row), 1 (a row from zeros) or 2."""
    del layer_ref                     # the BlockSpecs' own
    x_ref, w_ref = refs[:2]
    tail_ref, out_ref, new_ref, xs_ref, ys_ref, code_ref = refs[2 + bias:]
    f32 = jnp.float32
    count = xs_ref.shape[0]
    base = pl.program_id(1) * count

    @pl.when(pl.program_id(1) == 0)
    def _():                          # a row that stands at no slot: zeros
        out_ref[...] = jnp.zeros(out_ref.shape, f32)

    for i in range(count):
        row = row_ref[base + i]
        at = jnp.maximum(row, 0)
        xs_ref[i:i + 1, :] = x_ref[pl.ds(at, 1), :]
        code_ref[i:i + 1, :] = jnp.full(
            (1, code_ref.shape[1]), jnp.where(row < 0, 0, 1 + keep_ref[at]),
            jnp.int32)
    code = code_ref[:, :1]
    live, keep = code > 0, code > 1
    old = [tail_ref[j].astype(f32) for j in range(taps - 1)]
    # the window's rows, oldest first: the tail's k - 1, then the token
    win = [jnp.where(keep, t, 0.0) for t in old] + [xs_ref[...]]
    acc = sum(w_ref[j:j + 1, :] * win[j] for j in range(taps))
    ys_ref[...] = jax.nn.silu(refs[2][...] + acc if bias else acc)
    for j in range(taps - 1):
        new_ref[j] = jnp.where(live, win[j + 1], old[j]).astype(new_ref.dtype)
    for i in range(count):
        row = row_ref[base + i]

        @pl.when(row >= 0)
        def _():
            out_ref[pl.ds(row, 1), :] = ys_ref[i:i + 1, :]


def conv_tile(rows, channels, slots_a_step=None, lanes=None):
    """``(slots a grid step, channels a tile)`` of the tail's kernel: whole
    sublane tiles of slots in either dtype (16), whole lane tiles that
    divide the channels (all of them where 128 does not)."""
    count = -(-(slots_a_step or CONV_SLOTS) // 16) * 16
    if channels % 128:
        return count, channels
    most = lanes or CONV_TILE_BYTES // (4 * rows)
    return count, 128 * _divisor(channels // 128, most // 128)


def _conv_step_pallas(x, w, bias, conv, layer, slots, keep, interpret=False,
                      slots_a_step=None, lanes=None):
    """The same step with the pool aliased to the output, a block of slots
    at a time (above). The sink's tail is left as it was and a row on the
    sink reads zeros: nobody reads either."""
    rows, c = x.shape
    taps, total = w.shape[0], conv.shape[2]
    f32 = jnp.float32
    count, ct = conv_tile(rows, c, slots_a_step, lanes)
    groups = -(-total // count)
    # the row at each slot, by comparison (a scatter here is a loop on the
    # TPU); the sink (the last slot) has none
    ids = jnp.arange(groups * count, dtype=jnp.int32)[:, None]
    row_of = jnp.max(jnp.where(
        (slots.astype(jnp.int32)[None, :] == ids) & (ids != total - 1),
        jnp.arange(rows, dtype=jnp.int32)[None, :], -1), axis=1)
    tile = lambda ci, g, *_: (0, ci)                  # noqa: E731
    block = lambda ci, g, layer_ref, *_: (layer_ref[0], 0, g, ci)  # noqa
    ops = [x.astype(conv.dtype).astype(f32), w.astype(f32)]
    specs = [pl.BlockSpec((rows, ct), tile), pl.BlockSpec((taps, ct), tile)]
    if bias is not None:
        ops.append(bias.astype(f32).reshape(1, c))
        specs.append(pl.BlockSpec((1, ct), tile))
    pool = pl.BlockSpec((None, taps - 1, count, ct), block)
    # two buffers of the token's, the result's and the pool's blocks in and
    # out, the scratch and the window's rows in float32
    held = 4 * rows * ct * 4 + 4 * (taps - 1) * count * ct \
        * conv.dtype.itemsize + (4 + 2 * taps) * count * ct * 4
    out, conv = pl.pallas_call(
        functools.partial(_conv_tail_kernel, taps=taps,
                          bias=bias is not None),
        out_shape=[jax.ShapeDtypeStruct((rows, c), f32),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(c // ct, groups),
            in_specs=specs + [pool],
            out_specs=[pl.BlockSpec((rows, ct), tile), pool],
            scratch_shapes=[pltpu.VMEM((count, ct), f32),
                            pltpu.VMEM((count, ct), f32),
                            pltpu.VMEM((count, 128), jnp.int32)]),
        # operands count the scalar-prefetch three: the pool is the last
        input_output_aliases={3 + len(ops): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=min(max(held + (8 << 20), 16 << 20),
                                 100 << 20)),
        interpret=interpret, name="conv_tail_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row_of,
      keep.astype(jnp.int32), *ops, conv)
    return out, conv


CONV_STEPS = {
    "xla": _conv_step_xla,
    "pallas": _conv_step_pallas,
    "pallas_interpret": functools.partial(_conv_step_pallas, interpret=True),
}


def conv_step(x, w, bias, conv, layer, slots, keep, step=None):
    """One token for each row: ``x`` [rows, channels] behind the tail of
    its slot (zeros where ``keep`` [rows] is false), the tail shifted one on.
    ``step``: one of :data:`CONV_STEPS` (None: by platform; the serving
    forwards resolve theirs through the engine's ``module_registry``, kind
    ``conv_step``). -> ``(silu of the convolution [rows, channels] float32,
    conv)``."""
    return (step or CONV_STEPS[default_impl()])(x, w, bias, conv, layer,
                                                slots, keep)


def conv_piece(rows, w, bias, conv, layer, slot, keep, n):
    """One piece of ONE sequence: ``rows`` [q, channels], zero beyond its
    ``n`` rows, behind the tail of ``slot`` (zeros where ``keep`` is false);
    the tail left is the last ``kernel - 1`` inputs behind row ``n``. ->
    ``(silu of the convolution [q, channels] float32, conv)``."""
    q, k = rows.shape[0], w.shape[0]
    tail = jnp.where(keep, conv[layer, :, slot], 0)
    ext = jnp.concatenate([tail, rows.astype(conv.dtype)])
    acc = sum(w[j] * ext[j:j + q].astype(jnp.float32) for j in range(k))
    out = jax.nn.silu(acc if bias is None else bias + acc)
    # the last k - 1 inputs behind row n: the old tail's where n is
    # shorter than it
    conv = conv.at[layer, :, slot].set(
        jax.lax.dynamic_slice_in_dim(ext, n, k - 1))
    return out, conv


def _conv_pieces_xla(x, w, bias, conv, layer, pieces, chunk):
    """:func:`conv_pieces` as a loop of :func:`conv_piece` in XLA: the CPU's
    form, and what the kernel is held against."""
    row0, length, slots, fresh, count = pieces
    t = x.shape[0]
    x = jnp.pad(x, ((0, chunk), (0, 0)))

    def piece(i, carry):
        conv, out_all = carry
        r0, n = row0[i], length[i]
        valid = (jnp.arange(chunk) < n)[:, None]
        rows = jnp.where(valid, jax.lax.dynamic_slice_in_dim(x, r0, chunk),
                         0)
        out, conv = conv_piece(rows, w, bias, conv, layer, slots[i],
                               jnp.logical_not(fresh[i]), n)
        out_all = jax.lax.dynamic_update_slice_in_dim(
            out_all, jnp.where(
                valid, out, jax.lax.dynamic_slice_in_dim(out_all, r0, chunk)),
            r0, 0)
        return conv, out_all

    conv, out_all = jax.lax.fori_loop(
        0, count, piece,
        (conv, jnp.zeros((t + chunk, x.shape[1]), jnp.float32)))
    return out_all[:t], conv


# The pieces' kernel walks the PIECES, to their count, inside one call (as
# ``ops/kda.py``'s ``kda_piece`` does), and everything it moves is a copy of
# its own in whole tiles of what XLA already holds, so that the compiler
# lays nothing out anew: ``x`` [T, channels] and ``out`` [T, channels] stay
# two-dimensional with the rows on the sublanes, the pool keeps its slots
# there. A piece is seen through a FRAME of ``chunk + 16`` rows that starts
# at a multiple of 16 (a tile of either dtype) at or before its first row:
#
# * the frame of ``x`` comes into one of two buffers (the next piece's while
#   this one is computed) and is widened to float32 a strip of lanes at a
#   time behind 8 spare rows, the slot's tail is written over the three rows
#   before the piece's first (single rows of float32 by a dynamic index,
#   which Mosaic allows where it allows no such row of a packed block), and
#   tap ``j`` is the strip read ``j`` rows on: static, unaligned reads;
# * the slot's tail comes and goes as the whole block of PIECE_SLOTS slots
#   that holds it (Mosaic copies no single row of the tiled pool), kept in
#   float32 while the block stays (consecutive pieces of one slot, and
#   pieces of neighbouring slots, never go through HBM), and goes back when
#   the block changes or the pieces end. The block that leaves goes out of
#   one buffer while ANOTHER block's rows come into the other, and every
#   copy out is waited for before the next one starts: a block that comes
#   back is read only after its own copy out has landed;
# * the result goes out in whole 8-row tiles, the tiles that hold a live
#   row, in copies of 2^k tiles. The first and the last of them may hold
#   other pieces' rows: they are read back (through the aliased output,
#   after the piece before has landed) and added, the piece's own rows
#   being zero there and the others' zero here.
#
# A piece on the sink reads zeros and leaves no tail, as the tail's kernel
# has it. PIECES_TILE_BYTES: what a grid step's buffers may hold; all of the
# channels at both cells' widths (35 MB at Solar's 24,576 x 80 rows).
PIECE_SLOTS = 16
PIECE_LANES = 256
PIECES_TILE_BYTES = 48 << 20


def _conv_pieces_kernel(layer_ref, row0_ref, length_ref, slot_ref, fresh_ref,
                        count_ref, w_ref, *refs, taps, bias, lanes):
    """Grid step ``c``: channel tile ``c`` of EVERY live piece, one after
    another. ``xw`` [2, frame, ct] the frames of ``x`` as they come, ``xf``
    [8 + frame, lanes] float32 a strip of one behind its tail, ``yw`` [2,
    frame, ct] the results on their way out, ``edge`` [2, 8, ct] the first
    and the last tile as HBM holds them, ``blk`` [2, taps - 1, slots, ct]
    a block of the pool in and out, ``blkf`` the one held, in float32."""
    b_ref = refs[0] if bias else None
    (x_hbm, zero_hbm, pool_hbm, y_hbm, out_hbm, xw, xf, yw, edge, blk, blkf,
     xsem, ysem, esem, bsem) = refs[bias:]
    # out's zeros and the pool are aliased to the outputs, and both are read
    # where an earlier piece wrote them: through the outputs' refs
    del zero_hbm, pool_hbm
    f32 = jnp.float32
    k1 = taps - 1
    frame, ct = xw.shape[1:]
    tiles = frame // 8
    held_slots, total = blk.shape[2], out_hbm.shape[2]
    tp = x_hbm.shape[0]
    cols = pl.ds(pl.multiple_of(pl.program_id(0) * ct, ct), ct) \
        if ct % 128 == 0 else slice(None)
    layer, count = layer_ref[0], count_ref[0]

    def start_of(i):
        """The frame's first row: the multiple of 16 at or before ``row0``,
        or the last one a whole frame starts at."""
        return pl.multiple_of(
            jnp.minimum(row0_ref[i] // 16 * 16, tp - frame), 16)

    def x_copy(i, par):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(start_of(i), frame), cols], xw.at[par],
            xsem.at[par])

    def block_of(i):
        """The first slot of the block that holds piece ``i``'s."""
        return slot_ref[i] // held_slots * held_slots

    def block_copy(b0, out):
        at = (layer, slice(None),
              pl.ds(pl.multiple_of(b0, held_slots), held_slots), cols)
        return pltpu.make_async_copy(blk.at[1], out_hbm.at[at], bsem.at[1]) \
            if out else pltpu.make_async_copy(out_hbm.at[at], blk.at[0],
                                              bsem.at[0])

    def span(i):
        """``(first tile, tiles)`` of the frame that hold piece ``i``'s
        rows."""
        e = row0_ref[i] - start_of(i)
        first = e // 8
        return first, jnp.maximum((e + length_ref[i] + 7) // 8 - first, 1)

    def y_copies(i, par):
        """``(bit set, copy)`` for each power of two up to the frame's
        tiles: the largest run first."""
        first, m = span(i)
        out = []
        for bit in reversed(range(tiles.bit_length())):
            size = 1 << bit
            at = first + ((m >> (bit + 1)) << (bit + 1))
            at = pl.multiple_of(jnp.minimum(at, tiles - size) * 8, 8)
            out.append((((m >> bit) & 1) == 1, pltpu.make_async_copy(
                yw.at[par, pl.ds(at, size * 8)],
                y_hbm.at[pl.ds(start_of(i) + at, size * 8), cols],
                ysem.at[par, bit])))
        return out

    def y_wait(i, par):
        for on, copy in y_copies(i, par):
            pl.when(on)(copy.wait)

    def edge_copies(i):
        first, m = span(i)
        return [pltpu.make_async_copy(
            y_hbm.at[pl.ds(start_of(i) + pl.multiple_of(at * 8, 8), 8), cols],
            edge.at[n], esem.at[n])
            for n, at in enumerate((first, first + m - 1))]

    def put_back(b0, pending):
        """The held block goes out: once the copy out before it has."""
        pl.when(pending == 1)(block_copy(b0, True).wait)
        blk[1] = blkf[...].astype(blk.dtype)
        block_copy(b0, True).start()

    pl.when(count > 0)(x_copy(0, 0).start)

    def piece(i, carry):
        held, pending = carry
        par = jax.lax.rem(i, 2)
        x_copy(i, par).wait()
        pl.when(i + 1 < count)(x_copy(i + 1, 1 - par).start)
        slot = slot_ref[i]
        real = slot != total - 1
        b0 = block_of(i)
        row = slot - b0
        change = real & (b0 != held)

        @pl.when(change)
        def _():
            # ANOTHER block's rows: the copy in may pass the copy out
            pl.when(held >= 0)(lambda: put_back(held, pending))
            block_copy(b0, False).start()
            block_copy(b0, False).wait()
            blkf[...] = blk[0].astype(f32)

        pending = jnp.where(change, (held >= 0).astype(jnp.int32), pending)
        held = jnp.where(change, b0, held)

        e = row0_ref[i] - start_of(i)
        n = length_ref[i]
        keep = real & (fresh_ref[i] == 0)
        at = jax.lax.broadcasted_iota(jnp.int32, (frame, 1), 0)
        live = (at >= e) & (at < e + n)

        def strip(s, _):
            ls = pl.ds(pl.multiple_of(s * lanes, lanes), lanes) \
                if lanes % 128 == 0 else slice(None)
            xf[8:, :] = xw[par, :, ls].astype(f32)
            # the tail over the rows before the piece's first, then the
            # last taps - 1 inputs behind row n: the old tail's where n is
            # shorter than it
            for j in range(k1):
                xf[pl.ds(8 - k1 + e + j, 1), :] = jnp.where(
                    keep, blkf[j, pl.ds(row, 1), ls], 0.0)

            @pl.when(real)
            def _():
                for j in range(k1):
                    blkf[j, pl.ds(row, 1), ls] = xf[
                        pl.ds(8 - k1 + e + n + j, 1), :]

            acc = sum(w_ref[j:j + 1, ls] * xf[8 - k1 + j:8 - k1 + j + frame, :]
                      for j in range(taps))
            y = jax.nn.silu(acc + b_ref[:, ls] if bias else acc)
            yw[par, :, ls] = jnp.where(live, y, 0.0)
            return 0

        jax.lax.fori_loop(0, ct // lanes, strip, 0)
        # the piece before has landed: the tiles it shares are HBM's now
        pl.when(i >= 1)(lambda: y_wait(i - 1, 1 - par))
        first, m = span(i)
        reads = edge_copies(i)
        reads[0].start()
        pl.when(m > 1)(reads[1].start)
        reads[0].wait()
        rows = pl.ds(pl.multiple_of(first * 8, 8), 8)
        yw[par, rows, :] = yw[par, rows, :] + edge[0]

        @pl.when(m > 1)
        def _():
            reads[1].wait()
            rows = pl.ds(pl.multiple_of((first + m - 1) * 8, 8), 8)
            yw[par, rows, :] = yw[par, rows, :] + edge[1]

        for on, copy in y_copies(i, par):
            pl.when(on)(copy.start)
        return held, pending

    held, pending = jax.lax.fori_loop(
        0, count, piece, (jnp.int32(-1), jnp.int32(0)))

    @pl.when(count >= 1)
    def _():
        y_wait(count - 1, jax.lax.rem(count - 1, 2))

    @pl.when(held >= 0)
    def _():
        put_back(held, pending)
        block_copy(held, True).wait()


def piece_frame(chunk):
    """The rows a piece of at most ``chunk`` is seen through: whole tiles of
    either dtype from the tile its first row stands in."""
    return -(-chunk // 16) * 16 + 16


def _piece_channel_bytes(frame, itemsize, taps, slots=PIECE_SLOTS):
    """What a grid step of the pieces' kernel holds a channel: two frames
    of ``x`` and of the results, the two edge tiles, a block of the pool in,
    out and in float32, the taps and the bias in two buffers."""
    return 2 * frame * (itemsize + 4) + 2 * 8 * 4 \
        + (taps - 1) * slots * (2 * itemsize + 4) + 2 * (taps + 1) * 4


def conv_pieces_tile(channels, frame, itemsize, taps, most=None):
    """``(channels a grid step, lanes a strip)`` of the pieces' kernel: the
    most whole lane tiles that divide the channels and whose buffers stay
    under PIECES_TILE_BYTES (all of them where 128 does not divide)."""
    if channels % 128:
        return channels, channels
    most = most or PIECES_TILE_BYTES // _piece_channel_bytes(frame, itemsize,
                                                             taps)
    ct = 128 * _divisor(channels // 128, most // 128)
    return ct, 128 * _divisor(ct // 128, PIECE_LANES // 128)


def _conv_pieces_pallas(x, w, bias, conv, layer, pieces, chunk,
                        interpret=False, channels=None, lanes=None):
    """:func:`conv_pieces` as ONE ``pallas_call`` over all the live pieces
    (``conv_pieces``): ``x`` stays in HBM as it came, the pool is aliased
    to the output, ``out`` is written over zeros (above)."""
    row0, length, slots, fresh, count = pieces
    t, c = x.shape
    taps, total = w.shape[0], conv.shape[2]
    if total > PIECE_SLOTS and (total - 1) % PIECE_SLOTS:
        # a slot beyond the pool's last whole block: no aligned copy holds it
        return _conv_pieces_xla(x, w, bias, conv, layer, pieces, chunk)
    f32, i32 = jnp.float32, jnp.int32
    frame = piece_frame(chunk)
    # whole tiles of rows, and a frame at least
    tp = max(-(-t // 16) * 16, frame)
    x = x.astype(conv.dtype)
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
    size = conv.dtype.itemsize
    ct, strip = conv_pieces_tile(c, frame, size, taps, channels)
    strip = lanes or strip
    held_slots = min(PIECE_SLOTS, total)
    tile = lambda ci, *_: (0, ci)                     # noqa: E731
    ops = [w.astype(f32)]
    specs = [pl.BlockSpec((taps, ct), tile)]
    if bias is not None:
        ops.append(bias.astype(f32).reshape(1, c))
        specs.append(pl.BlockSpec((1, ct), tile))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    held = ct * _piece_channel_bytes(frame, size, taps, held_slots) \
        + (8 + frame) * strip * 4
    out, conv = pl.pallas_call(
        functools.partial(_conv_pieces_kernel, taps=taps,
                          bias=bias is not None, lanes=strip),
        out_shape=[jax.ShapeDtypeStruct((tp, c), f32),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(c // ct,),
            in_specs=specs + [in_hbm] * 3, out_specs=[in_hbm, in_hbm],
            scratch_shapes=[
                pltpu.VMEM((2, frame, ct), conv.dtype),
                pltpu.VMEM((8 + frame, strip), f32),
                pltpu.VMEM((2, frame, ct), f32),
                pltpu.VMEM((2, 8, ct), f32),
                pltpu.VMEM((2, taps - 1, held_slots, ct), conv.dtype),
                pltpu.VMEM((taps - 1, held_slots, ct), f32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2, (frame // 8).bit_length())),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,))]),
        # operands count the scalar-prefetch six: out's zeros and the pool
        # are the last two
        input_output_aliases={7 + len(ops): 0, 8 + len(ops): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(max(held + (16 << 20), 32 << 20),
                                 100 << 20)),
        interpret=interpret, name="conv_pieces",
    )(jnp.asarray(layer, i32).reshape(1), row0.astype(i32),
      length.astype(i32), slots.astype(i32), fresh.astype(i32),
      jnp.asarray(count, i32).reshape(1), *ops, x,
      jnp.zeros((tp, c), f32), conv)
    return out[:t], conv


CONV_PIECES = {
    "xla": _conv_pieces_xla,
    "pallas": _conv_pieces_pallas,
    "pallas_interpret": functools.partial(_conv_pieces_pallas,
                                          interpret=True),
}


def conv_pieces(x, w, bias, conv, layer, pieces, chunk, form=None):
    """The convolution alone over the pieces of a flat batch (``pieces`` as
    :func:`chunked_scan` takes them: of at most ``chunk`` rows, on rows of
    their own), before a mixer's recurrence. ``x`` [T, channels]; a piece
    of ``n`` rows stands behind the tail of its slot (zeros where it is
    ``fresh``) and leaves the last ``kernel - 1`` inputs behind row ``n``
    there. ``form``: one of :data:`CONV_PIECES` (None: by platform; the
    serving forwards resolve theirs through the engine's
    ``module_registry``, kind ``conv_pieces``). -> ``(out [T, channels]
    float32, zero where no piece lies; conv)``."""
    return (form or CONV_PIECES[default_impl()])(x, w, bias, conv, layer,
                                                 pieces, chunk)


def _split_xbc(out, cfg):
    """The convolution's output ``[rows, channels]`` as x ``[rows, g, hp]``
    (a group's heads side by side), B and C ``[rows, g, n]``."""
    di, g, n = cfg.ssm_d_inner, cfg.ssm_n_groups, cfg.ssm_state_size
    rows = out.shape[0]
    return (out[:, :di].reshape(rows, g, di // g),
            out[:, di:di + g * n].reshape(rows, g, n),
            out[:, di + g * n:].reshape(rows, g, n))


def _per_head(v, cfg):
    """``[rows, h]`` (or ``[h]``) -> ``[rows, g, hp]``: a head's value over
    its ``p`` channels, in the state's lane order."""
    g, p = cfg.ssm_n_groups, cfg.mamba_head_dim
    lead = v.shape[:-1]
    return jnp.repeat(v.reshape(*lead, g, -1), p, axis=-1)


def _dt_decay(dt, p, cfg):
    """``(dt [rows, h] after bias and softplus, dt A [rows, h])``, float32;
    ``time_step_limit`` (0, inf): no clamp."""
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))
    return dtv, dtv * -jnp.exp(p["A_log"].astype(jnp.float32))


# ----------------------------------------------------------- the decode step
def _state_step_xla(pool, layer, slots, keep, decay, dtx, b, c):
    """``pool[layer, slots]`` one step on: gather, update, scatter (a
    scatter on the loop-carried pool is in place; the gather is a copy).
    ``keep`` [rows] float32 0/1: 0 starts the row from zeros."""
    state = pool[layer, slots] * keep[:, None, None, None]
    new = state * decay[:, :, None, :] + b[:, :, :, None] * dtx[:, :, None, :]
    y = jnp.einsum("sgnq,sgn->sgq", new, c,
                   precision=jax.lax.Precision.HIGHEST)
    return y, pool.at[layer, slots].set(new.astype(pool.dtype))


def _state_step_kernel(layer_ref, slots_ref, keep_ref, decay_ref, dtx_ref,
                       bt_ref, ct_ref, st_ref, y_ref, out_ref, *, groups):
    """One row's whole state ``[g, n, hp]``: read once, written once."""
    del layer_ref, slots_ref          # the BlockSpecs' own
    keep = keep_ref[pl.program_id(0)].astype(jnp.float32)
    for g in range(groups):
        new = (st_ref[g].astype(jnp.float32) * keep) * decay_ref[g:g + 1, :] \
            + bt_ref[:, g:g + 1] * dtx_ref[g:g + 1, :]
        out_ref[g] = new.astype(out_ref.dtype)
        y_ref[g:g + 1, :] = jnp.sum(new * ct_ref[:, g:g + 1], axis=0,
                                    keepdims=True)


def _state_step_pallas(pool, layer, slots, keep, decay, dtx, b, c,
                       interpret=False):
    """The same step with the pool aliased to the output: block ``[layer,
    slots[row]]`` of the pool in, the same block out. B and C come
    transposed, ``[rows, n, g]`` (a column a group: what the sublanes of a
    state block are multiplied by). Rows that share a slot (the sink) write
    it one after another; nobody reads it."""
    rows, g, n, hp = (slots.shape[0], *pool.shape[2:])
    row = lambda s, *_: (s, 0, 0)                     # noqa: E731
    state = lambda s, layer_ref, slots_ref, keep_ref: (  # noqa: E731
        layer_ref[0], slots_ref[s], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(rows,),
        in_specs=[pl.BlockSpec((None, g, hp), row),
                  pl.BlockSpec((None, g, hp), row),
                  pl.BlockSpec((None, n, g), row),
                  pl.BlockSpec((None, n, g), row),
                  pl.BlockSpec((None, None, g, n, hp), state)],
        out_specs=[pl.BlockSpec((None, g, hp), row),
                   pl.BlockSpec((None, None, g, n, hp), state)])
    block = g * n * hp * pool.dtype.itemsize
    y, pool = pl.pallas_call(
        functools.partial(_state_step_kernel, groups=g),
        out_shape=[jax.ShapeDtypeStruct((rows, g, hp), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch three: the pool is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(max(6 * block + (8 << 20), 16 << 20),
                                 100 << 20)),
        interpret=interpret, name="ssm_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      keep.astype(jnp.int32), decay, dtx, b.swapaxes(1, 2), c.swapaxes(1, 2),
      pool)
    return y, pool


STATE_STEPS = {
    "xla": _state_step_xla,
    "pallas": _state_step_pallas,
    "pallas_interpret": functools.partial(_state_step_pallas,
                                          interpret=True),
}


def decode_step(xbc, dt, p, ssm, conv, layer, slots, fresh, cfg, step=None,
                conv_fn=None):
    """One token for each row. ``xbc`` [rows, channels] and ``dt`` [rows, h]
    as ``in_proj`` gives them; ``p`` the layer's leaves; ``ssm`` / ``conv``
    the pools, ``layer`` the Mamba layer, ``slots`` [rows] each row's state
    slot (the sink for a row that is padding), ``fresh`` [rows] bool: the
    row is its sequence's first token. ``step``: one of :data:`STATE_STEPS`
    (None: by platform; the serving forwards resolve theirs through the
    engine's ``module_registry``, kind ``ssm_step``); ``conv_fn``: one of
    :data:`CONV_STEPS`, likewise. -> ``(y [rows, d_inner] float32, ssm,
    conv)``."""
    step = step or STATE_STEPS[default_impl()]
    keep = jnp.logical_not(fresh)
    with scope("ssm_conv"):
        out, conv = conv_step(xbc, *_conv_weights(p), conv, layer, slots,
                              keep, conv_fn)
    with scope("ssm_scan"):
        x, b, c = _split_xbc(out, cfg)
        dtv, da = _dt_decay(dt, p, cfg)
        y, ssm = step(ssm, layer, slots, keep.astype(jnp.float32),
                      _per_head(jnp.exp(da), cfg), _per_head(dtv, cfg) * x,
                      b, c)
        y = y + _per_head(p["D"].astype(jnp.float32), cfg) * x
    return y.reshape(y.shape[0], -1), ssm, conv


# --------------------------------------------------------- the chunked scan
def _piece_scan(x, b, c, dtv, da, state, cfg, dtype):
    """One piece of ONE sequence, ``q`` rows (a row that is not the piece's
    has ``dt`` 0: it decays nothing and adds nothing): the quadratic form
    inside the piece, ``state`` [g, n, hp] in and out. x [q, g, hp]; b, c
    [q, g, n]; dtv, da [q, h] float32. The products take their operands in
    ``dtype`` (the activations') and accumulate in float32."""
    q, g, hp = x.shape
    hg = cfg.mamba_num_heads // g
    f32 = jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32,
                           precision=jax.lax.Precision.HIGHEST
                           if dtype == f32 else None)
    cum = jnp.cumsum(da, axis=0)                               # [q, h]
    seen = jnp.tril(jnp.ones((q, q), bool))
    # exp(cum_i - cum_j) for j <= i: what row j's input has decayed to at i
    lmat = jnp.exp(jnp.where(seen[:, :, None],
                             cum[:, None, :] - cum[None, :, :], -jnp.inf))
    cb = mm("ign,jgn->gij", c.astype(dtype), b.astype(dtype))  # [g, q, q]
    w = lmat.transpose(2, 0, 1).reshape(g, hg, q, q) * cb[:, None]
    dtx = (_per_head(dtv, cfg) * x).reshape(q, g, hg, -1)      # [q,g,hg,p]
    y = mm("gkij,jgkp->igkp", w.astype(dtype), dtx.astype(dtype))
    # the state the piece entered with, decayed to each row
    st = state.astype(f32).reshape(g, -1, hg, hp // hg)        # [g,n,hg,p]
    into = jnp.exp(cum).reshape(q, g, hg)
    y = y + into[..., None] * mm("ign,gnkp->igkp", c.astype(f32), st,
                                 precision=jax.lax.Precision.HIGHEST)
    # and what it leaves: everything decayed to the last row
    left = jnp.exp(cum[-1][None] - cum).reshape(q, g, hg)
    new = jnp.exp(cum[-1]).reshape(g, 1, hg, 1) * st + mm(
        "jgn,jgkp->gnkp", b.astype(dtype),
        (left[..., None] * dtx).astype(dtype))
    return y.reshape(q, g, hp), new.reshape(state.shape)


def chunked_scan(xbc, dt, p, ssm, conv, layer, pieces, cfg):
    """The chunks of two tokens or more of a flat batch. ``xbc`` [T,
    channels], ``dt`` [T, h]; ``pieces`` = ``(row0, length, slot, fresh)``
    each [pieces], live ones first, and their count (``ragged.ssm_pieces``):
    rows ``row0 .. row0 + length`` of the flat axis are ``length <= chunk``
    consecutive tokens of the sequence in state slot ``slot``, and ``fresh``
    says the first of them is the sequence's first. -> ``(y [T, d_inner]
    float32, zero where no piece lies; ssm; conv)``."""
    row0, length, slots, fresh, count = pieces
    q = cfg.ssm_chunk_size
    t, dtype = xbc.shape[0], xbc.dtype
    w, bias = _conv_weights(p)
    d_skip = _per_head(p["D"].astype(jnp.float32), cfg)
    # a window of q rows from any row0 < T stays inside the padded arrays
    xbc = jnp.pad(xbc, ((0, q), (0, 0)))
    dt = jnp.pad(dt, ((0, q), (0, 0)))

    def piece(i, carry):
        ssm, conv, y_all = carry
        r0, n, slot = row0[i], length[i], slots[i]
        keep = jnp.logical_not(fresh[i])
        valid = (jnp.arange(q) < n)[:, None]
        with scope("ssm_conv"):
            rows = jnp.where(valid, jax.lax.dynamic_slice_in_dim(xbc, r0, q),
                             0)
            out, conv = conv_piece(rows, w, bias, conv, layer, slot, keep, n)
        # ssm_chunk inside ssm_scan: the pieces' own time, apart from the
        # state step of the one-token rows beside them (decode_step)
        with scope("ssm_scan"), scope("ssm_chunk"):
            x, b, c = _split_xbc(out, cfg)
            dtv, da = _dt_decay(jax.lax.dynamic_slice_in_dim(dt, r0, q), p,
                                cfg)
            dtv, da = jnp.where(valid, dtv, 0), jnp.where(valid, da, 0)
            state = jnp.where(keep, ssm[layer, slot], 0)
            y, state = _piece_scan(x, b, c, dtv, da, state, cfg, dtype)
            y = (y + d_skip * x).reshape(q, -1)
            ssm = ssm.at[layer, slot].set(state.astype(ssm.dtype))
            y_all = jax.lax.dynamic_update_slice_in_dim(
                y_all, jnp.where(
                    valid, y, jax.lax.dynamic_slice_in_dim(y_all, r0, q)),
                r0, 0)
        return ssm, conv, y_all

    ssm, conv, y_all = jax.lax.fori_loop(
        0, count, piece,
        (ssm, conv, jnp.zeros((t + q, cfg.ssm_d_inner), jnp.float32)))
    return y_all[:t], ssm, conv


# ----------------------------------------------- lightning linear attention
class _HeadGroups(NamedTuple):
    """What :func:`_piece_scan` and :func:`_per_head` read of a config, for
    a mixer whose every head is a group of its own."""
    mamba_num_heads: int
    ssm_n_groups: int
    mamba_head_dim: int


def lightning_decay(heads: int):
    """[heads] float32: the LOG of each head's decay a token, ``-2^(-8 (h +
    1) / heads)`` (Lightning Attention's slopes: half-lives from under a
    token to some hundreds)."""
    return -jnp.exp2(-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                     / heads)


def lightning_step(q, k, v, pool, layer, slots, fresh, step=None):
    """One token for each row: ``q`` (scaled), ``k``, ``v`` [rows, h, d];
    ``pool`` [layers, slots + 1, h, d, d] float32, the key's channels on
    the sublanes; ``slots`` / ``fresh`` / ``step`` as :func:`decode_step`'s.
    -> ``(o [rows, h, d] float32, pool)``."""
    step = step or STATE_STEPS[default_impl()]
    f32 = jnp.float32
    rows, h, d = q.shape
    with scope("la_step"):
        decay = jnp.broadcast_to(jnp.exp(lightning_decay(h))[None, :, None],
                                 (rows, h, d))
        return step(pool, layer, slots, jnp.logical_not(fresh).astype(f32),
                    decay, v.astype(f32), k.astype(f32), q.astype(f32))


def lightning_pieces(q, k, v, pool, layer, pieces, chunk: int, dtype):
    """The chunks of two tokens or more of a flat batch: ``q`` (scaled),
    ``k``, ``v`` [T, h, d]; ``pieces`` as :func:`chunked_scan` takes them,
    of at most ``chunk`` rows; the products' operands in ``dtype``. -> ``(o
    [T, h, d] float32, zero where no piece lies; pool)``."""
    row0, length, slots, fresh, count = pieces
    t, h, d = q.shape
    dims = _HeadGroups(h, h, d)
    log_decay = lightning_decay(h)
    q, k, v = (jnp.pad(a, ((0, chunk), (0, 0), (0, 0))) for a in (q, k, v))
    window = lambda a, r0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        a, r0, chunk)

    def piece(i, carry):
        pool, y_all = carry
        r0, slot = row0[i], slots[i]
        valid = (jnp.arange(chunk) < length[i])[:, None]
        # la_chunk: the pieces' own time, apart from the state step of the
        # one-token rows beside them (lightning_step)
        with scope("la_chunk"):
            dtv = jnp.broadcast_to(valid.astype(jnp.float32), (chunk, h))
            state = jnp.where(fresh[i], 0.0, pool[layer, slot])
            y, state = _piece_scan(
                jnp.where(valid[..., None], window(v, r0), 0), window(k, r0),
                window(q, r0), dtv, dtv * log_decay, state, dims, dtype)
            pool = pool.at[layer, slot].set(state.astype(pool.dtype))
            y_all = jax.lax.dynamic_update_slice_in_dim(
                y_all, jnp.where(valid[..., None], y, window(y_all, r0)),
                r0, 0)
        return pool, y_all

    pool, y_all = jax.lax.fori_loop(
        0, count, piece, (pool, jnp.zeros((t + chunk, h, d), jnp.float32)))
    return y_all[:t], pool


def gated_norm(y, z, scale, cfg):
    """``RMSNorm(y * silu(z))`` in ``ssm_n_groups`` groups of ``d_inner / g``
    channels (``eps`` the model's), times the ``d_inner``-wide weight.
    float32 in, float32 out."""
    rows, g = y.shape[0], cfg.ssm_n_groups
    u = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(rows, g, -1)
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                          + cfg.rms_norm_eps)
    return u.reshape(rows, -1) * scale.astype(jnp.float32)
