"""Block-sparse attention's three device steps before the attention itself
(InfLLM-v2, MiniCPM4's technical report arXiv:2506.07900;
``ModelConfig.sparse_block_topk``): the POOLED keys, the block SCORES of a
tile of query rows, and the exact SELECTION of the blocks a row and KV group
reads. What attends over the selection is ``ops/paged_attention.py`` as it
stands (``inference/v2/bsa.py`` hands it a mask or a list of pages).

Sizes (:class:`Sizes`, MiniCPM4's ``sparse_config``): a WINDOW is ``kernel``
(32) consecutive keys, one every ``stride`` (16): window ``j`` covers tokens
``[stride j, stride j + kernel)`` and exists once its last token does; a
BLOCK is ``block`` (64) tokens, ``block / stride`` (4) windows START in it,
and ``kernel / stride - 1`` (1) more reach into it from the block before.

* **pooled keys** (:func:`pool_write`) — window ``j``'s key is the MEAN of a
  KV head's ``kernel`` keys. It is cached beside K and V in a pool of its
  own, ``[layers, pages, block / stride, KVH, D]``, at the page its window
  STARTS in, so a page that is freed or requeued takes its pooled keys with
  it. A forward writes the windows that END in the rows it brings; up to
  ``kernel - 1`` of a window's keys lie in earlier chunks or the page
  before and are read back from the K pool through the block table (no tail
  buffer).
* **scores** (:func:`block_scores`) — per query head the softmax of ``q .
  c_j / sqrt(D)`` over the windows wholly at or before the row, summed over
  the heads of a KV group, and a block's score the MAXIMUM over the windows
  that overlap it. ``jax.numpy`` as it stands: a tile is one sequence's
  rows (an atom, or a one-token row), walked one after another so that only
  one tile's ``[heads, rows, windows]`` probabilities stand in HBM (the
  kernel form that keeps them in VMEM is not written: PERF.md section 7).
* **selection** (:func:`select_blocks`) — per row and KV group
  ``topk`` blocks among those it may see: the first ``init``, the
  ``window`` ending with its own, and the best of the rest by score (ties
  to the lower block; all while they are no more than ``topk``); a row whose
  context is under ``dense_len`` reads every block. The forced blocks are
  set above any score and ``ops/sparse_index.select_topk`` (the exact
  ``k``-th largest by bit passes) does the rest: a block's score where it had
  a token's.

``impl`` is the paged kernels' word, ``pallas`` | ``pallas_interpret`` |
``xla``; only the selection has a kernel form.
"""
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import sparse_index

# above any block score (a sum of ``group`` probabilities), finite so that
# its bits order as a float's do
FORCED = np.float32(3e38)


class Sizes(NamedTuple):
    """The block-sparse attention's sizes (``ModelConfig.sparse_block_*``)."""
    block: int
    kernel: int
    stride: int
    init: int
    window: int
    topk: int
    dense_len: int

    @classmethod
    def of(cls, cfg) -> "Sizes":
        return cls(cfg.sparse_block_size, cfg.sparse_block_kernel,
                   cfg.sparse_block_stride, cfg.sparse_block_init,
                   cfg.sparse_block_window, cfg.sparse_block_topk,
                   cfg.sparse_block_dense_len)

    @property
    def per_block(self) -> int:
        """Windows that START in a block."""
        return self.block // self.stride

    @property
    def reach(self) -> int:
        """Windows of the block before that reach into a block."""
        return self.kernel // self.stride - 1

    @property
    def row_pages(self) -> int:
        """Pages a one-token row's table has: the most a row reads, under
        the selection or under ``dense_len``."""
        return max(self.topk, -(-(self.dense_len - 1) // self.block))

    def windows_seen(self, pos):
        """Windows wholly at or before position ``pos`` (array or int)."""
        return jnp.maximum((pos + 1 - self.kernel) // self.stride + 1, 0)


# =============================================================== pooled keys
def window_key(keys):
    """keys [n, kernel, KVH, D] -> [n, KVH, D] float32: a window's pooled
    key, the MEAN of its keys."""
    return keys.astype(jnp.float32).mean(1)


def pool_write(ck_pool, k_pool, layer, block_tables, seq, pos, live,
               sizes: Sizes):
    """The pooled keys of the windows that END in the rows of a forward.
    ``ck_pool`` [L, pages, block / stride, KVH, D]; ``k_pool`` [L, slots,
    KVH, D] AFTER the layer's keys were written; ``seq`` / ``pos`` / ``live``
    [n]: each row's line of ``block_tables`` [S, Bps], position and whether
    it is a token. A row at position ``p`` ends window ``(p + 1 - kernel) /
    stride`` where that is whole; its keys are read through the table."""
    bs, kern = sizes.block, sizes.kernel
    pages = ck_pool.shape[1]
    ends = live & ((pos + 1) % sizes.stride == 0) & (pos + 1 >= kern)
    first = jnp.maximum(pos + 1 - kern, 0)
    at = first[:, None] + jnp.arange(kern)[None, :]              # [n, kern]
    table = block_tables[jnp.minimum(seq, block_tables.shape[0] - 1)]
    slots = jnp.take_along_axis(table, at // bs, axis=1) * bs + at % bs
    slots = jnp.where(ends[:, None], slots, 0)
    mean = window_key(k_pool[layer, slots])                      # [n, KVH, D]
    j = first // sizes.stride
    page = jnp.take_along_axis(table, (first // bs)[:, None], axis=1)[:, 0]
    return ck_pool.at[layer, jnp.where(ends, page, pages),
                      j % sizes.per_block].set(
        mean.astype(ck_pool.dtype), mode="drop")


def seq_pooled_keys(ck_pool, layer, block_tables):
    """[S, W, KVH, D]: every sequence slot's pooled keys of ``layer`` in
    window order, a page at a time through its table (what lies past a
    sequence's whole windows is whatever the pages hold, and no row may see
    it)."""
    keys = ck_pool[layer, block_tables]         # [S, Bps, per_block, KVH, D]
    return keys.reshape(block_tables.shape[0], -1, *keys.shape[3:])


# ===================================================================== scores
def _to_blocks(p, sizes: Sizes):
    """p [..., W] (W = blocks x per_block) -> [..., blocks]: the maximum
    over the windows that overlap each block."""
    per, reach = sizes.per_block, sizes.reach
    own = p.reshape(*p.shape[:-1], -1, per)
    best = own.max(-1)
    if reach:
        into = own[..., per - reach:].max(-1)      # the block BEFORE's last
        into = jnp.concatenate(
            [jnp.zeros_like(into[..., :1]), into[..., :-1]], axis=-1)
        best = jnp.maximum(best, into)
    return best


def group_sum(p):
    """p [KVH, G, R, W], a head's probabilities -> [KVH, R, W]: the sum over
    the heads of a KV group, which share ONE selection."""
    return p.sum(1)


def tile_scores(q, c, pos, sizes: Sizes):
    """One tile: q [R, H, D], c [W, KVH, D] (its sequence's pooled keys),
    pos [R] -> [R, KVH, blocks] float32. A window a row may not see scores
    0; a row that sees none scores 0 everywhere."""
    r, h, d = q.shape
    w, kvh, _ = c.shape
    logits = jnp.einsum("rkgd,wkd->kgrw", q.reshape(r, kvh, h // kvh, d), c,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    seen = jnp.arange(w)[None, :] < sizes.windows_seen(pos)[:, None]
    logits = jnp.where(seen, logits, -jnp.inf)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(logits - jnp.where(jnp.isfinite(top), top,
                                                   0.0)), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    return jnp.swapaxes(_to_blocks(group_sum(p), sizes), 0, 1)


def block_scores(q, c_seq, tile_seq, pos, qlen, sizes: Sizes):
    """q [A, R, H, D]; c_seq [S, W, KVH, D] (:func:`seq_pooled_keys`);
    tile_seq [A] the slot a tile's rows belong to; pos [A, R] each row's
    position; qlen [A] the tile's live rows (0: a dead tile, which costs
    nothing) -> [A, R, KVH, blocks] float32."""
    blocks = c_seq.shape[1] // sizes.per_block
    shape = (q.shape[1], c_seq.shape[2], blocks)

    def tile(args):
        q_t, seq, pos_t, n = args
        return jax.lax.cond(
            n > 0, lambda: tile_scores(q_t, c_seq[seq], pos_t, sizes),
            lambda: jnp.zeros(shape, jnp.float32))

    return jax.lax.map(tile, (q, tile_seq, pos, qlen))


# ================================================================== selection
def forced_blocks(pos, blocks: int, sizes: Sizes):
    """pos [...] -> bool [..., blocks]: the first ``init`` blocks and the
    ``window`` ending with the row's own."""
    own = (pos // sizes.block)[..., None]
    b = jnp.arange(blocks)
    return (b < sizes.init) | ((b > own - sizes.window) & (b <= own))


def select_blocks(scores, pos, qlen, sizes: Sizes, impl: str = "xla"):
    """scores [A, R, KVH, blocks] float32; pos [A, R] each row's position
    (under 0: a dead row); qlen [A]: the tile's live rows -> int8 [A, R,
    KVH, blocks]: 1 at the blocks the row and KV group reads."""
    a, r, kvh, blocks = scores.shape
    own = pos // sizes.block                                      # [A, R]
    tiles = lambda t: jnp.repeat(t, kvh, axis=0)    # noqa: E731  (a, head)
    s = jnp.where(forced_blocks(pos, blocks, sizes)[:, :, None, :], FORCED,
                  scores)
    s = jnp.swapaxes(s, 1, 2).reshape(a * kvh, r, blocks)
    sel = sparse_index.select_topk(s, tiles(own), tiles(qlen),
                                   k=min(sizes.topk, blocks), impl=impl,
                                   name="bsa_select")
    sel = jnp.swapaxes(sel.reshape(a, kvh, r, blocks), 1, 2)
    live = (jnp.arange(r)[None, :] < qlen[:, None]) & (pos >= 0)
    seen = (jnp.arange(blocks) <= own[..., None]) & live[..., None]
    dense = (pos + 1 < sizes.dense_len)[..., None]
    return jnp.where((dense & seen)[:, :, None, :], jnp.int8(1), sel)


def page_tables(sel, block_tables, pos, sizes: Sizes):
    """One-token rows: sel [S, KVH, blocks] (the rows' selection), each
    row's line of ``block_tables`` [S, Bps] and position ``pos`` [S] (under
    0: no row) -> ``(tables [S x KVH, row_pages] int32, lens [S x KVH])``:
    per (row, KV head) the PAGES it reads in rising order of block (its own
    block last) and the keys they hold for it, so that a one-row tile of the
    paged kernel over that table is the attention over the selection."""
    s, kvh, blocks = sel.shape
    k = sizes.row_pages
    top = sparse_index.positions_from_mask(sel.reshape(s * kvh, blocks), k=k)
    count = jnp.sum(top < blocks, axis=1, dtype=jnp.int32)
    line = jnp.repeat(block_tables, kvh, axis=0)
    tables = jnp.take_along_axis(line, jnp.minimum(top, blocks - 1), axis=1)
    at = jnp.repeat(pos, kvh)
    lens = jnp.where(at >= 0, (count - 1) * sizes.block
                     + at % sizes.block + 1, 0)
    return tables, lens
