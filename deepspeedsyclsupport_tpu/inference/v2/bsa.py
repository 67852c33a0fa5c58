"""Block-sparse attention chosen from pooled keys, on the serving path
(``ModelConfig.sparse_block_topk``; InfLLM-v2 under the sizes MiniCPM4's
``sparse_config`` publishes; the ``*`` layers of minicpm_sala).

Beside K and V a layer caches the POOLED keys, the mean of every window of
``sparse_block_kernel`` keys a KV head (``kv_cache.BlockedKV.ck``, at the
page the window starts in). A query scores its sequence's windows with the
attention's OWN queries (no indexer, no projection of its own), the 16 heads
of a KV group share one selection of ``sparse_block_topk`` blocks, and the
block is the pool's page (``block_size`` must equal ``sparse_block_size``):
a selection IS a block table.

Five labels reach the device trace: ``bsa_pool`` (the pooled keys' write),
``bsa_score`` (their gather a sequence, the scores and the pooling to
blocks), ``bsa_select``, and ``bsa_attend`` (whatever gathers, masks and
attends; the one-token rows' part under ``bsa_rows`` inside it).

Two routes, by the chunk's length as everywhere on this path:

* a chunk of two tokens or more, cut into atoms: a tile of the scores and of
  the selection is an atom, and the ragged paged kernel runs under the
  selection of BLOCKS, a KV head's rows under their own head's
  (``ragged_prefill_attention``'s ``sel`` with a kv-head axis: no mask of
  keys is made outside the kernel). It visits
  every cached page of the atom and keeps the selected. (The union of an
  atom's pages as a list, which the same kernel could walk, is not written:
  over seeded weights the 128 rows of an atom choose nearly every visible
  block between them; ``bsa_pages`` counts the union.)
* a one-token chunk (every row of ``decode_forward``): each (row, KV head)
  is a one-row tile of the paged kernel over a TABLE OF ITS OWN, the pages
  it selected in rising order (``sparse_block.page_tables``): what it reads
  of K and V is ``sparse_block_topk`` pages whatever its context.

An engine whose attention takes no atoms (``prefill_attn`` ``xla`` or
``flash``: the CPU's, the tests') runs every row as a tile of one row
through ``jax.numpy``.

What the forwards count on the device (``BlockedKV.bsa``,
:data:`COUNTS`), summed over the sparse layers of the last forward.
"""
import jax.numpy as jnp

from ...monitor.mfu import scope
from ...ops import sparse_block
from .dsa import kernel_impl

#: ``BlockedKV.bsa`` [7] int32, of the LAST forward, summed over its sparse
#: layers: live rows; the windows they may see; the (row, KV group, token)
#: triples attended; the pages read (an atom's: the union of its rows' a KV
#: head; a one-token row's: its own a KV head); the blocks visible to those
#: tiles (pages / visible = the share of the context that is read); and the
#: ONE-TOKEN rows' part of the triples and of the pages
COUNTS = ("bsa_rows", "bsa_windows", "bsa_pairs", "bsa_pages",
          "bsa_visible_blocks", "bsa_row_pairs", "bsa_row_pages")


def _counts(sel, pos, qlen, sizes, union: bool):
    """:data:`COUNTS` of one layer's tiles: sel [A, R, KVH, blocks], pos
    [A, R], qlen [A]. ``union``: a tile reads the union of its rows' pages
    (an atom); else each row its own."""
    i32 = jnp.int32
    live = (jnp.arange(pos.shape[1])[None, :] < qlen[:, None]) & (pos >= 0)
    chosen = jnp.sum(sel, axis=-1, dtype=i32)                  # [A, R, KVH]
    own_gap = (sizes.block - 1 - pos % sizes.block)[..., None]
    pairs = jnp.where(live[..., None], chosen * sizes.block - own_gap, 0)
    seen = jnp.where(live, pos // sizes.block + 1, 0)
    kvh = sel.shape[2]
    if union:
        pages = jnp.sum(jnp.any(sel != 0, axis=1), dtype=i32)
        visible = jnp.sum(seen.max(1), dtype=i32) * kvh
    else:
        pages = jnp.sum(jnp.where(live[..., None], chosen, 0), dtype=i32)
        visible = jnp.sum(seen, dtype=i32) * kvh
    pairs = jnp.sum(pairs, dtype=i32)
    zero = jnp.zeros((), i32)
    return jnp.stack([
        jnp.sum(live, dtype=i32),
        jnp.sum(jnp.where(live, sizes.windows_seen(pos), 0), dtype=i32),
        pairs, pages, visible,
        zero if union else pairs, zero if union else pages])


def _per_group(out, kvh: int):
    """out [tiles x KVH, R, H, D], tile (t, g) computed under KV head g's
    selection -> [tiles, R, H, D]: each head from its own group's tile."""
    n, r, h, d = out.shape
    out = out.reshape(n // kvh, kvh, r, kvh, h // kvh, d)
    pick = jnp.arange(kvh)
    return out[:, pick, :, pick].transpose(1, 2, 0, 3, 4).reshape(
        n // kvh, r, h, d)


def attend_atoms(q, c_seq, k_cache, v_cache, layer, ctx, sizes, impl):
    """The chunks of two tokens or more: per atom the scores, the selection
    and the ragged kernel under each KV head's mask. -> ``([T, H, D] in
    packed rows (the one-token and padding rows gather the reserved dead
    atom's zeros), counts)``."""
    from ...ops.paged_attention import ragged_prefill_attention

    s = ctx.block_tables.shape[0]
    q_at = q[ctx.atom_qidx]                                 # [A, BQ, H, D]
    bq = q_at.shape[1]
    tile_seq = jnp.clip(ctx.token_seq[ctx.atom_qidx[:, 0]], 0, s - 1)
    pos = ctx.atom_pos0[:, None] + jnp.arange(bq)[None, :]
    with scope("bsa_score"):
        scores = sparse_block.block_scores(q_at, c_seq, tile_seq, pos,
                                           ctx.atom_qlen, sizes)
    with scope("bsa_select"):
        sel = sparse_block.select_blocks(scores, pos, ctx.atom_qlen, sizes,
                                         impl)
    with scope("bsa_attend"):
        # the blocks as they were chosen, a block's rows on the lanes: the
        # kernel widens a step's to its keys, nothing here does
        out = ragged_prefill_attention(
            q_at, k_cache, v_cache, ctx.atom_tables, ctx.atom_pos0,
            ctx.atom_qlen, block_size=ctx.block_size, layer=layer, impl=impl,
            sel=jnp.transpose(sel, (0, 2, 3, 1)), name="bsa_prefill")
        out = out.reshape(-1, *out.shape[2:])[ctx.atom_inv]
    return out, _counts(sel, pos, ctx.atom_qlen, sizes, union=True)


def attend_rows(q, c_seq, k_cache, v_cache, layer, block_tables, seq_lens,
                block_size: int, sizes, impl: str):
    """One-token rows, one a sequence slot: q [S, H, D], ``seq_lens`` [S]
    the slot's length WITH the row's token (0: no row). The scores of each
    row as a tile of its own, the selection of all rows as one tile, each
    (row, KV head)'s pages as a table, and the one-row tile of the paged
    kernel over it. -> ``([S, H, D], counts)``."""
    from ...ops.paged_attention import paged_decode_attention

    s = q.shape[0]
    kvh = k_cache.shape[-2]
    pos = seq_lens - 1
    with scope("bsa_score"):
        scores = sparse_block.block_scores(
            q[:, None], c_seq, jnp.arange(s), pos[:, None],
            (seq_lens > 0).astype(jnp.int32), sizes)     # [S, 1, KVH, blocks]
    with scope("bsa_select"):
        sel = sparse_block.select_blocks(
            scores[:, 0][None], pos[None], jnp.full((1,), s, jnp.int32),
            sizes, impl)[0]                               # [S, KVH, blocks]
        tables, lens = sparse_block.page_tables(sel, block_tables, pos,
                                                sizes)
    with scope("bsa_attend"), scope("bsa_rows"):
        out = paged_decode_attention(
            jnp.repeat(q, kvh, axis=0), k_cache, v_cache, tables, lens,
            block_size=block_size, impl=impl, layer=layer, name="bsa_rows")
        out = _per_group(out[:, None], kvh)[:, 0]
    return out, _counts(sel[:, None], pos[:, None],
                        (seq_lens > 0).astype(jnp.int32), sizes, union=False)


def attend_tokens(q, c_seq, k_cache, v_cache, layer, ctx, sizes):
    """Every packed row on its own through ``jax.numpy`` (a tile of one
    row): the route of an attention that takes no atoms."""
    from .model import _paged_attention

    t, h, d = q.shape
    s = ctx.block_tables.shape[0]
    kvh = k_cache.shape[-2]
    live = ctx.token_seq < s
    pos = jnp.where(live, ctx.token_pos, -1)[:, None]
    qlen = live.astype(jnp.int32)
    with scope("bsa_score"):
        scores = sparse_block.block_scores(
            q[:, None], c_seq, jnp.minimum(ctx.token_seq, s - 1), pos, qlen,
            sizes)
    with scope("bsa_select"):
        sel = sparse_block.select_blocks(scores, pos, qlen, sizes)
    with scope("bsa_attend"):
        out = jnp.stack([_paged_attention(
            q, k_cache[layer], v_cache[layer], ctx.token_seq, ctx.token_pos,
            ctx.block_tables, ctx.block_size,
            sel=jnp.repeat(sel[:, 0, g], sizes.block, axis=-1))
            for g in range(kvh)], axis=1)                   # [T, KVH, H, D]
        out = _per_group(out.reshape(t * kvh, 1, h, d), kvh)[:, 0]
    return out, _counts(sel, pos, qlen, sizes, union=False)


def _write_pooled(pools, layer, block_tables, seq, pos, live, sizes):
    k_cache, v_cache, ck = pools
    with scope("bsa_pool"):
        ck = sparse_block.pool_write(ck, k_cache, layer, block_tables, seq,
                                     pos, live, sizes)
    with scope("bsa_score"):
        c_seq = sparse_block.seq_pooled_keys(ck, layer, block_tables)
    return (k_cache, v_cache, ck), c_seq


def ragged_attend(q, pools, layer, ctx, cfg, impl_name: str):
    """Attention of one ``ragged_forward`` layer over the selected blocks:
    q [T, H, D], the pools ``(k, v, ck, counts)`` AFTER this layer's K and V
    were written, ``ctx`` a ``PrefillAttnContext``. -> ``([T, H, D],
    pools)``: the pooled keys written, the counts added to."""
    sizes = sparse_block.Sizes.of(cfg)
    *pools, counted = pools
    s = ctx.block_tables.shape[0]
    pools, c_seq = _write_pooled(pools, layer, ctx.block_tables,
                                 ctx.token_seq, ctx.token_pos,
                                 ctx.token_seq < s, sizes)
    k_cache, v_cache, _ = pools
    impl = kernel_impl(impl_name)
    if ctx.atom_qidx is None or impl == "xla":
        out, n = attend_tokens(q, c_seq, k_cache, v_cache, layer, ctx, sizes)
        return out, (*pools, counted + n)
    out, n = attend_atoms(q, c_seq, k_cache, v_cache, layer, ctx, sizes,
                          impl)
    out_dec, n_dec = attend_rows(
        q[ctx.dec_row], c_seq, k_cache, v_cache, layer, ctx.block_tables,
        ctx.dec_len, ctx.block_size, sizes, impl)
    # a slot with no one-token chunk scatters out of range (dropped)
    rows = jnp.where(ctx.dec_len > 0, ctx.dec_row, q.shape[0])
    return (out.at[rows].set(out_dec, mode="drop"),
            (*pools, counted + n + n_dec))


def decode_attend(q, pools, layer, block_tables, seq_lens, block_size: int,
                  cfg, impl: str):
    """Attention of one ``decode_forward`` layer: every row a one-token
    row. ``impl``: the ``decode_attn`` entry's name, the kernels' word as it
    stands."""
    sizes = sparse_block.Sizes.of(cfg)
    *pools, counted = pools
    s = q.shape[0]
    pools, c_seq = _write_pooled(pools, layer, block_tables, jnp.arange(s),
                                 seq_lens - 1, seq_lens > 0, sizes)
    out, n = attend_rows(q, c_seq, pools[0], pools[1], layer, block_tables,
                         seq_lens, block_size, sizes, impl)
    return out, (*pools, counted + n)
