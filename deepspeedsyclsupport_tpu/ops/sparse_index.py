"""The sparse-attention indexer's two device steps (DeepSeek-V3.2's recipe,
``ModelConfig.index_topk``): the SCORES of a tile of query rows against their
sequence's cached indexer keys, and the exact SELECTION of the best ``topk``
of them a row. What attends over the selection is ``ops/paged_attention.py``
(a per-row mask in the ragged kernel) and ``inference/v2/dsa.py`` (the
gather of a one-token row's selected keys).

* **scores** — ``I(t, s) = scale x sum_j w_t[j] relu(qI_t[j] . kI_s)`` over
  the ``index_heads`` heads ``j``: one ``[heads x rows, d] x [d, keys]``
  product a tile, the relu, the heads' weights and their sum on the way out,
  so the ``[heads, rows, keys]`` products never reach HBM (at 16 heads they
  are 16 x the result). A tile of rows is ONE sequence's (an atom of the
  ragged batch, or a one-token row alone); its keys are that sequence's,
  gathered once a forward in position order. The keys a grid step takes
  follow the tile's rows (:func:`score_keys`: 512 under an atom of 128 rows,
  thousands under one row, where a step of 512 would be all grid). Dead
  tiles and key tiles past the tile's last position cost a grid step and
  nothing else.
* **selection** — per row the ``k`` largest scores among the positions it
  may see (``s <= t``, each row of a tile at a position of its OWN: an
  atom's rows stand at consecutive ones, the one-token rows of a forward
  each at its sequence's last), ties to the LOWER position, all of them
  while there are no more than ``k``: EXACT, and without a sort, which the
  chip has no instruction for. A score's bit pattern, sign folded, orders as the score
  does, so the ``k``-th largest is found a bit at a time: 32 passes of
  compare-and-count over the row (``count(key >= candidate) >= k`` keeps
  the bit), then, only where the ``k``-th value is tied, 17 more over the
  positions of the tied. The result is a mask ``[rows, keys]`` int8. A
  tile's passes walk only the keys up to its longest row's last position.
* **positions** — the mask's set as ``[rows, k]`` positions for a gather
  (:func:`positions_from_mask`): counts by 128-lane group, then each output
  slot's group and its rank inside it by compare-and-count. Dense vector
  work and two small products: no sort, no scatter, no gather.

Each has an exact ``jax.numpy`` twin (``*_reference``) for the CPU and the
tests; ``impl`` is the paged kernels' word: ``pallas`` | ``pallas_interpret``
| ``xla``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MIN = np.int32(-2 ** 31)
# what one grid step of the scores kernel may hold in VMEM of its products,
# its key tile and its result (score_keys), rows one step of the selection
# kernel takes (an int8 tile's 32 sublanes), keys one pass of its loops walks
# at a time
SCORE_STEP_BYTES = 8 << 20
SELECT_ROWS = 32
SELECT_CHUNK = 2048
_VMEM_LIMIT = 96 << 20


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


# ===================================================================== scores
def index_scores_reference(q_idx, w, k_seq, tile_seq, *, scale: float):
    """q_idx [A, R, Hi, Di], w [A, R, Hi] (float32), k_seq [S, C, Di] (each
    sequence slot's indexer keys in position order), tile_seq [A] (the slot
    a tile's rows belong to) -> scores [A, R, C] float32, every position
    scored whatever the row may see."""
    k = k_seq[tile_seq].astype(jnp.float32)
    s = jnp.einsum("arhd,acd->arhc", q_idx.astype(jnp.float32), k)
    return (jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None]
            ).sum(2) * scale + 0.0


def _scores_kernel(seq_ref, hi_ref, q_ref, w_ref, k_ref, out_ref, *,
                   heads: int, scale: float):
    a, c = pl.program_id(0), pl.program_id(1)
    keys = k_ref.shape[1]
    rows = out_ref.shape[1]

    @pl.when(c * keys < hi_ref[a])
    def _():
        s = jax.lax.dot_general(                      # [Hi x R, keys]
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0]            # w: [Hi x R, 1]
        out_ref[0] = s.reshape(heads, rows, keys).sum(0) * scale + 0.0

    @pl.when(c * keys >= hi_ref[a])
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)


def score_keys(rows: int, heads: int, dim: int, itemsize: int,
               c: int) -> int:
    """Keys one grid step of the scores kernel takes under a tile of
    ``rows`` rows, by the tile's shape alone: the most (128 x a power of
    two, no more than the ``c`` there are) whose ``[heads x rows, keys]``
    float32 products, key tile and result (both held twice: the pipeline's
    two buffers, lanes and sublanes padded as VMEM pads them) fit
    ``SCORE_STEP_BYTES``. 512 under 16 heads x 128 rows; 8,192 under 16
    heads x ONE row, whose step of 512 would be 96 grid steps a row at 48 k
    for a ``[16, 64] x [64, 512]`` product each."""
    a_key = heads * rows * 4 + 2 * itemsize * _ceil_to(dim, 128) \
        + 2 * 4 * _ceil_to(rows, 8)
    keys = 128
    while 2 * keys * a_key <= SCORE_STEP_BYTES:
        keys *= 2
    return min(keys, _ceil_to(c, 128))


def index_scores_pallas(q_idx, w, k_seq, tile_seq, tile_hi, *, scale: float,
                        interpret: bool = False):
    """:func:`index_scores_reference` as one kernel; ``tile_hi`` [A]: the
    keys a tile's last row may see (0: a dead tile). Key tiles at or past it
    are written as zeros and their keys not fetched again. C is padded to
    whole key tiles (:func:`score_keys`) here and cut back."""
    a, r, hi, di = q_idx.shape
    s_, c, _ = k_seq.shape
    keys = score_keys(r, hi, di, k_seq.dtype.itemsize, c)
    c_pad = _ceil_to(c, keys)
    if c_pad != c:
        k_seq = jnp.pad(k_seq, ((0, 0), (0, c_pad - c), (0, 0)))
    # heads-major rows: the product's [Hi x R, keys] splits into [Hi, R, .]
    q2 = jnp.transpose(q_idx, (0, 2, 1, 3)).reshape(a, hi * r, di)
    w2 = jnp.transpose(w.astype(jnp.float32), (0, 2, 1)).reshape(
        a, hi * r, 1)

    def k_map(i, j, seq_ref, hi_ref):
        # a tile past the last position re-names the last one needed: the
        # pipeline does not fetch a block it already holds
        last = jnp.maximum(hi_ref[i] - 1, 0) // keys
        return seq_ref[i], jnp.minimum(j, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(a, c_pad // keys),
        in_specs=[
            pl.BlockSpec((1, hi * r, di), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, hi * r, 1), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, keys, di), k_map)],
        out_specs=pl.BlockSpec((1, r, keys), lambda i, j, *_: (i, 0, j)))
    out = pl.pallas_call(
        functools.partial(_scores_kernel, heads=hi, scale=scale),
        out_shape=jax.ShapeDtypeStruct((a, r, c_pad), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="dsa_index_scores",
    )(jnp.asarray(tile_seq, jnp.int32), jnp.asarray(tile_hi, jnp.int32),
      q2, w2, k_seq)
    return out if c_pad == c else out[..., :c]


def index_scores(q_idx, w, k_seq, tile_seq, tile_hi, *, scale: float,
                 impl: str = "xla"):
    if impl == "xla":
        return index_scores_reference(q_idx, w, k_seq, tile_seq, scale=scale)
    return index_scores_pallas(q_idx, w, k_seq, tile_seq, tile_hi,
                               scale=scale,
                               interpret=impl == "pallas_interpret")


# ================================================================== selection
def _reach(pos0, qlen, rows: int):
    """[A, R] int32: how many positions row r of tile a may see, 0 for a
    dead row. ``pos0`` [A]: the tile's rows stand at consecutive positions,
    row r at ``pos0[a] + r``; ``pos0`` [A, R]: each row at a position of its
    own (under 0: a dead row). Either way a row is live while ``r <
    qlen[a]`` and sees the positions up to its own."""
    pos0, r = jnp.asarray(pos0, jnp.int32), jnp.arange(rows, dtype=jnp.int32)
    pos = pos0 if pos0.ndim == 2 else pos0[:, None] + r
    live = r[None] < jnp.asarray(qlen, jnp.int32)[:, None]
    return jnp.where(live, jnp.maximum(pos + 1, 0), 0)


def _seen(pos0, qlen, rows: int, c: int):
    """[A, R, C] bool: position c is one row r of tile a may see."""
    return jnp.arange(c)[None, None, :] < _reach(pos0, qlen, rows)[..., None]


def select_topk_reference(scores, pos0, qlen, *, k: int):
    """scores [A, R, C] float32; row r of tile a stands at position
    ``pos0[a] + r`` (``pos0`` [A]) or ``pos0[a, r]`` (``pos0`` [A, R]:
    :func:`_reach`) and is live while ``r < qlen[a]`` -> int8 [A, R, C]: 1
    at the ``k`` best positions the row may see (``<=`` its own), ties to
    the lower position (``lax.top_k``'s rule); all it sees while they are no
    more than ``k``; a dead row selects nothing."""
    a, r, c = scores.shape
    seen = _seen(pos0, qlen, r, c)
    masked = jnp.where(seen, scores, -jnp.inf).reshape(a * r, c)
    _, top = jax.lax.top_k(masked, min(k, c))
    chosen = jnp.zeros((a * r, c), bool).at[
        jnp.arange(a * r)[:, None], top].set(True).reshape(a, r, c)
    return jnp.logical_and(chosen, seen).astype(jnp.int8)


def _select_kernel(hi_ref, s_ref, reach_ref, out_ref, key_ref, *, k: int,
                   chunk: int):
    a, t = pl.program_id(0), pl.program_id(1)
    rows, c = key_ref.shape
    # keys any row of this step may see; a step of dead rows walks nothing
    n_chunks = (hi_ref[a, t] + chunk - 1) // chunk
    reach = reach_ref[0]                                    # [rows, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

    def at(j):
        return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)

    def fill(j, _):
        """The chunk's sortable keys: a float's bits with the sign folded
        order as the float; what the row may not see sorts below all."""
        bits = jax.lax.bitcast_convert_type(s_ref[0, :, at(j)], jnp.int32)
        key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        key_ref[:, at(j)] = jnp.where(j * chunk + lane < reach, key, INT_MIN)
        return 0

    jax.lax.fori_loop(0, n_chunks, fill, 0)

    def count(pred):
        """[rows, 1] int32: the keys of the walked chunks that ``pred(key
        chunk, its positions)`` holds for. Summed lane tile on lane tile;
        ONE reduction across the lanes a walk."""
        width = min(chunk, 128)

        def body(j, acc):
            hit = pred(key_ref[:, at(j)], j * chunk + lane).astype(jnp.int32)
            for i in range(0, chunk, width):
                acc = acc + hit[:, i:i + width]
            return acc
        acc = jax.lax.fori_loop(0, n_chunks, body,
                                jnp.zeros((rows, width), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def value_bit(i, prefix):
        """The k-th largest key, a bit at a time from the top, in the order
        of UNSIGNED patterns (a signed key's with its sign bit turned)."""
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        ge = count(lambda key, _pos: key >= (cand ^ INT_MIN))
        return jnp.where(ge >= k, cand, prefix)

    prefix = jax.lax.fori_loop(0, 32, value_bit,
                               jnp.zeros((rows, 1), jnp.int32))
    kth = prefix ^ INT_MIN                      # INT_MIN: fewer than k seen
    above = count(lambda key, _pos: key > kth)
    tied = count(lambda key, _pos: key == kth)
    need = k - above                            # of the tied, lowest first

    def position_bit(i, last):
        """The largest position P with fewer than ``need`` tied keys BELOW
        it: the need-th tied key's own."""
        cand = last | jnp.left_shift(jnp.int32(1), 16 - i)
        below = count(lambda key, pos: jnp.logical_and(key == kth,
                                                       pos < cand))
        return jnp.where(below < need, cand, last)

    full = jnp.full((rows, 1), c, jnp.int32)
    # only a tile with a row whose k-th value is tied pays the second walk
    last = jax.lax.cond(
        jnp.sum(jnp.logical_and(tied > need, kth != INT_MIN)
                .astype(jnp.int32)) > 0,
        lambda: jax.lax.fori_loop(0, 17, position_bit,
                                  jnp.zeros((rows, 1), jnp.int32)),
        lambda: full)

    def emit(j, _):
        key, pos = key_ref[:, at(j)], j * chunk + lane
        pick = jnp.logical_or(key > kth, jnp.logical_and(key == kth,
                                                         pos <= last))
        pick = jnp.logical_and(pick, key != INT_MIN)
        out_ref[0, :, at(j)] = pick.astype(jnp.int8)
        return 0

    def blank(j, _):
        out_ref[0, :, at(j)] = jnp.zeros((rows, chunk), jnp.int8)
        return 0

    jax.lax.fori_loop(0, n_chunks, emit, 0)
    jax.lax.fori_loop(n_chunks, c // chunk, blank, 0)


def select_chunk(c: int) -> int:
    """Keys one pass of the selection kernel's loops walks at a time over
    rows of ``c`` keys."""
    return min(SELECT_CHUNK, _ceil_to(c, 128))


def select_topk_pallas(scores, pos0, qlen, *, k: int,
                       interpret: bool = False, name: str = "dsa_select"):
    """:func:`select_topk_reference` as one kernel: a grid step takes
    ``SELECT_ROWS`` rows of one tile, their scores whole in VMEM, each row's
    reach (:func:`_reach`) beside them, and walks up to the longest's."""
    a, r, c = scores.shape
    if c > 1 << 17:
        raise ValueError(f"{c} keys a row: the tie walk covers 2**17")
    rows, chunk = min(SELECT_ROWS, r), select_chunk(c)
    r_pad, c_pad = _ceil_to(r, rows), _ceil_to(c, chunk)
    reach = jnp.minimum(_reach(pos0, qlen, r), c)
    if (r_pad, c_pad) != (r, c):
        scores = jnp.pad(scores, ((0, 0), (0, r_pad - r), (0, c_pad - c)))
        reach = jnp.pad(reach, ((0, 0), (0, r_pad - r)))
    block = pl.BlockSpec((1, rows, c_pad), lambda i, j, *_: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(a, r_pad // rows),
        in_specs=[block,
                  pl.BlockSpec((1, rows, 1), lambda i, j, *_: (i, j, 0))],
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((rows, c_pad), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((a, r_pad, c_pad), jnp.int8),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(reach.reshape(a, r_pad // rows, rows).max(-1), scores,
      reach[..., None])
    return out if (r_pad, c_pad) == (r, c) else out[:, :r, :c]


def select_topk(scores, pos0, qlen, *, k: int, impl: str = "xla",
                name: str = "dsa_select"):
    """``name``: what a profile calls the kernel (the block selection of
    ``ops/sparse_block.py`` passes its own)."""
    if impl == "xla":
        return select_topk_reference(scores, pos0, qlen, k=k)
    return select_topk_pallas(scores, pos0, qlen, k=k,
                              interpret=impl == "pallas_interpret", name=name)


# ================================================================== positions
def positions_from_mask(mask, *, k: int):
    """mask [S, C] (nonzero: selected) -> [S, k] int32: the selected
    positions of each row in rising order, then ``C`` in the slots a row
    with fewer than ``k`` leaves over (of more than ``k`` the lowest ``k``).

    What a sort or a scatter would do, as dense vector work: the flags'
    running count inside each 128-lane group (a product with a triangle of
    ones), the groups' totals summed along the row, and for output slot j
    its GROUP by compare-and-count against those sums (the groups that end
    at or before j) and its LANE the same way on the group's 128 running
    counts, which a one-hot product picks out. Counts to 128 are exact in
    bfloat16, the sums are float32: every number here is a whole one."""
    s, c = mask.shape
    lanes = 128
    c_pad = _ceil_to(c, lanes)
    flags = jnp.pad(mask != 0, ((0, 0), (0, c_pad - c)))
    flags = flags.reshape(s, c_pad // lanes, lanes).astype(jnp.bfloat16)
    upper = (jnp.arange(lanes)[:, None] <= jnp.arange(lanes)[None, :])
    within = jnp.einsum("sgl,lm->sgm", flags, upper.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)   # running count
    count = within[..., -1].astype(jnp.int32)                 # [S, G]
    upto = jnp.cumsum(count, axis=-1)            # ... with the groups before
    slot = jnp.arange(k, dtype=jnp.int32)
    done = upto[:, None, :] <= slot[None, :, None]            # [S, k, G]
    group = done.sum(-1, dtype=jnp.int32)
    rank = slot[None] - jnp.where(done, count[:, None, :], 0).sum(-1)
    onehot = group[..., None] == jnp.arange(c_pad // lanes)
    counts = jnp.einsum("skg,sgl->skl", onehot.astype(jnp.bfloat16),
                        within.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)   # [S, k, 128]
    lane = (counts <= rank[..., None].astype(jnp.float32)).sum(
        -1, dtype=jnp.int32)
    return jnp.where(slot[None] < upto[:, -1:], group * lanes + lane, c)
