"""Grouped GEMM over rows sorted by expert — a Pallas TPU kernel whose ROW
TILE FITS THE EXPERT.

The serving path's sparse-expert MLP (``parallel/moe.moe_mlp_nodrop``) sorts
its (token, choice) rows by expert and multiplies each expert's rows by that
expert's three matrices: the reference's ``moe_scatter`` -> CUTLASS grouped
GEMM -> ``moe_gather`` (``inference/v2/kernels/ragged_ops/``,
``modules/implementations/moe/cutlass_multi_gemm.py``). ``jax.lax.ragged_dot``
does that on any backend; on the TPU it compiles to a custom call whose row
tile is 256 or 512 whatever an expert got, so a decode step's 2-4 rows an
expert and a chunk round's 30-100 cost the MXU whole tiles and the GEMMs ran
at half of what reading the weights once allows (PERF.md section 6, PR 36).

Kernel shape:

* the ROW TILE comes from the static shape (:func:`row_tile`): the smallest
  of 16 (a bf16 tile's sublanes) ... 128 at or above the mean rows an expert
  gets, so a decode program takes 16 and a chunk program 32-128;
* every expert's rows start on a tile boundary (:func:`tile_rows`: the sorted
  rows are laid out with each group padded to a multiple of the tile), so a
  tile has ONE expert and an expert's weights are read ONCE: consecutive
  tiles of one expert keep the weight block's index, and the pipeline copies
  nothing. Experts with no row have no tile; tiles past the last live one
  repeat its indices and skip the body;
* the weights ride as the whole stacked leaf ``[L, E, K, N]`` with ``layer``
  and the tile -> expert map as SCALAR-PREFETCH operands of their
  ``BlockSpec`` (as ``paged_attention._prefill_kernel`` takes the pool): no
  slice of a layer is ever materialised, and a block is whole in K and as
  wide in N as VMEM allows (:func:`col_tile`), double-buffered by the
  pipeline so the next expert's block is in flight while this one multiplies;
* gate and up projections share one read of the rows; the activation and
  their product happen on the float32 accumulators and ``[rows, F]`` is
  written once (:func:`grouped_glu`); the down projection is the same body
  without the epilogue (:func:`grouped_matmul`). Inputs and outputs keep the
  rows' dtype.

What the kernel leaves in rows no tile covers (past the last live tile) is
whatever the buffer held: the caller reads back only rows that had an expert.

:func:`default_impl` picks by platform: the kernel on the TPU, ``ragged_dot``
anywhere else (which is also the tests' reference; there the kernel runs in
interpret mode, as a fixture).
"""
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the row tiles there are: a bf16 tile packs 16 sublanes; past the MXU's 128
# rows a taller tile saves nothing a second visit of the same weights costs
ROW_TILES = (16, 32, 64, 128)
# what the double-buffered weight blocks of one grid step may take of VMEM
# (of the v5e's 128 MiB); the widest column tile under it is taken
_WEIGHT_VMEM_BUDGET = 40 << 20
_VMEM_CAP = 100 << 20


def default_impl() -> str:
    """``pallas`` on the TPU, ``xla`` (``jax.lax.ragged_dot``) elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def row_tile(rows: int, experts: int) -> int:
    """Rows of a tile, by the SHAPE: the smallest of :data:`ROW_TILES` at or
    above the mean rows an expert gets when ``rows`` (token, choice) rows
    are routed over ``experts`` (the router's whole width: a program that
    holds a share of them gets that share of the rows). OLMoE's decode step
    (256 rows over 64) and DeepSeek-V2's (384 over 160) take 16; the chunk
    programs 128 (6,144 over 64), 64 (3,072 over 64) and 32 (4,608 over
    160)."""
    return next((t for t in ROW_TILES if t * experts >= rows), ROW_TILES[-1])


def col_tile(k: int, n: int, mats: int, itemsize: int) -> int:
    """Columns of a weight block, whole in K: all ``n`` where ``mats``
    double-buffered ``[k, n]`` blocks fit the budget, else the widest
    multiple of 128 that divides ``n`` and fits (128 at the least)."""
    fits = _WEIGHT_VMEM_BUDGET // (2 * mats * k * itemsize)
    if n <= fits or n % 128:
        return n
    return max((c for c in range(128, n, 128) if n % c == 0 and c <= fits),
               default=128)


class RowTiles(NamedTuple):
    """Where the sorted rows lie once every group starts on a tile boundary.
    ``tile`` rows a tile (static); ``group`` [max tiles] the expert of each
    tile (tiles past the last live one repeat its expert); ``live`` [1] the
    tiles that hold a row: the kernel's row-tile visits; ``src`` [max tiles x
    tile] the sorted row each laid-out row shows (a pad row shows some other
    row: finite, never read back); ``dest`` [rows] the laid-out row of each
    sorted row (a row with no group keeps its own index)."""
    tile: int
    group: jnp.ndarray
    live: jnp.ndarray
    src: jnp.ndarray
    dest: jnp.ndarray


def tile_visits(group_sizes: jnp.ndarray, tile: int) -> jnp.ndarray:
    """Row-tile visits of groups of ``group_sizes`` rows: a few integer ops,
    summed over every leading axis (the forwards count theirs with it)."""
    return jnp.sum((group_sizes + tile - 1) // tile, dtype=jnp.int32)


def tile_rows(group_sizes: jnp.ndarray, sorted_group: jnp.ndarray,
              tile: int) -> RowTiles:
    """Lay ``len(sorted_group)`` rows, sorted by group, out in tiles of
    ``tile`` rows with each group padded to whole tiles. ``group_sizes`` [G]
    int32; ``sorted_group`` [rows] each sorted row's group, ``G`` for a row
    in none (they sort last)."""
    g, m = group_sizes.shape[0], sorted_group.shape[0]
    max_tiles = m // tile + g
    tiles = (group_sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    live = tile_end[-1]
    # a tile's group: the first whose tiles end past it (empty groups have
    # none); dead tiles take the last live tile's, so nothing is copied
    t = jnp.clip(jnp.arange(max_tiles), 0, jnp.maximum(live - 1, 0))
    group = jnp.minimum(
        jnp.sum(t[:, None] >= tile_end[None, :], axis=1, dtype=jnp.int32),
        g - 1)
    # how far a group's rows move: its first tile's first row less its
    # first sorted row
    start = jnp.cumsum(group_sizes) - group_sizes
    shift = (tile_end - tiles) * tile - start
    src = jnp.arange(max_tiles * tile) - jnp.repeat(shift[group], tile)
    dest = jnp.arange(m) + jnp.concatenate(
        [shift, jnp.zeros((1,), shift.dtype)])[sorted_group]
    return RowTiles(tile, group, live.astype(jnp.int32).reshape(1),
                    jnp.clip(src, 0, m - 1).astype(jnp.int32),
                    dest.astype(jnp.int32))


def _kernel(layer_ref, group_ref, live_ref, x_ref, *refs, act):
    """One program per (column tile, row tile): the tile's rows times its
    expert's block(s). Two weight refs: ``act(x wg) * (x wu)`` on the float32
    accumulators; one: ``x w``, or ``act(x w)`` where an ``act`` is given."""
    del layer_ref, group_ref      # the BlockSpecs' own
    *w_refs, out_ref = refs

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        x = x_ref[...]
        acc = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
               for w in w_refs]
        if len(acc) == 2:
            y = act(acc[0]) * acc[1]
        else:
            y = acc[0] if act is None else act(acc[0])
        out_ref[...] = y.astype(out_ref.dtype)


def _vmem_limit(tm: int, k: int, tn: int, mats: int, itemsize: int) -> int:
    """Scoped-VMEM limit stated for one grid step: the double-buffered
    weight, row and result blocks, the float32 accumulators and the
    epilogue's temporaries, with a quarter of room (a ceiling, not a
    reservation)."""
    need = (2 * mats * k * tn * itemsize + 2 * tm * (k + tn) * itemsize
            + (mats + 2) * tm * tn * 4)
    if need > _VMEM_CAP:
        raise ValueError(
            f"grouped GEMM blocks of {mats} x [{k}, {tn}] need ~{need >> 20} "
            f"MiB of VMEM (cap {_VMEM_CAP >> 20} MiB)")
    return min(max(need + need // 4 + (4 << 20), 16 << 20), _VMEM_CAP)


def _grouped_call(rows, weights: Sequence[jnp.ndarray], tiles: RowTiles,
                  layer, act, name: str, interpret=False):
    """``rows`` [max tiles x tile, K] laid out by ``tiles``; ``weights`` one
    or two stacked leaves ``[L, E, K, N]`` (or one layer's ``[E, K, N]``: a
    stack of one, read at 0) of the rows' dtype. Returns ``[., N]``."""
    if weights[0].ndim == 3:     # only one layer's leaf may be cast
        weights, layer = [w.astype(rows.dtype)[None] for w in weights], 0
    for w in weights:
        if w.dtype != rows.dtype:
            raise ValueError(
                f"a stack of expert weights must have the activations' "
                f"dtype ({w.dtype} != {rows.dtype}): hand one layer's slice "
                f"instead")
    m, k = rows.shape
    n = weights[0].shape[-1]
    tm, mats, itemsize = tiles.tile, len(weights), rows.dtype.itemsize
    tn = col_tile(k, n, mats, itemsize)

    def row_block(i, live_ref):   # a tile past the last live one: the last
        return jnp.clip(i, 0, jnp.maximum(live_ref[0] - 1, 0))

    def row_map(j, i, layer_ref, group_ref, live_ref):
        return row_block(i, live_ref), 0

    def w_map(j, i, layer_ref, group_ref, live_ref):
        return layer_ref[0], group_ref[i], 0, j

    def out_map(j, i, layer_ref, group_ref, live_ref):
        return row_block(i, live_ref), j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, m // tm),
        in_specs=[pl.BlockSpec((tm, k), row_map),
                  *(pl.BlockSpec((None, None, k, tn), w_map)
                    for _ in weights)],
        out_specs=pl.BlockSpec((tm, tn), out_map),
    )
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, k, tn, mats, itemsize)),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tiles.group, tiles.live,
      rows, *weights)


def grouped_glu(rows, w_gate, w_up, tiles: RowTiles, *, layer=0, act,
                interpret: bool = False):
    """``act(rows w_gate[g]) * (rows w_up[g])`` for each tile's expert ``g``
    over ONE read of the rows: ``[., K] -> [., F]``, ``grouped_glu`` in a
    profile."""
    return _grouped_call(rows, (w_gate, w_up), tiles, layer, act,
                         "grouped_glu", interpret)


def grouped_act(rows, w, tiles: RowTiles, *, layer=0, act,
                interpret: bool = False):
    """``act(rows w[g])`` for each tile's expert ``g``, the activation on
    the float32 accumulator: the up projection of a two-matrix expert,
    ``grouped_act`` in a profile."""
    return _grouped_call(rows, (w,), tiles, layer, act, "grouped_act",
                         interpret)


def grouped_matmul(rows, w, tiles: RowTiles, *, layer=0,
                   interpret: bool = False):
    """``rows w[g]`` for each tile's expert ``g``: ``grouped_matmul`` in a
    profile."""
    return _grouped_call(rows, (w,), tiles, layer, None, "grouped_matmul",
                         interpret)
