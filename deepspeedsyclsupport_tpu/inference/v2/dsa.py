"""Sparse attention behind a learned indexer, on the serving path
(``ModelConfig.index_topk``; DeepSeek-V3.2's recipe under the sizes
KeyeVL2's ``sa_config`` and GLM-5's config publish), over a pool of K and V
a KV head (Keye) or over a LATENT pool, one row a token for all heads and no
V (``ModelConfig.kv_lora_rank``: GLM-5): one module, the pool's kind by the
configuration.

Beside the attention's own rows a token gives the INDEXER's ``index_heads``
small queries ``qI``, its ONE key ``kI`` (behind a LayerNorm) and a weight a
head ``w``. The key and the weights are projections of the layer's normed
row; the queries too (Keye), or of latent attention's normed query latent
``c_q`` (``ModelConfig.index_q_latent``: the recipe's own, GLM-5's). Queries
and key are rotated over their leading ``index_rope_dim`` dims (0: their
whole width). ``kI`` is cached as the attention's rows are, in the pool's
last array at the token's slot (``kv_cache.BlockedKV.idx``). A query scores
every cached token of its sequence, ``I(t, s) = (heads x dim)^-1/2 x sum_j
w_t[j] relu(qI_t[j] . kI_s)``, keeps the ``index_topk`` best of those it may
see (ties to the lower position; all while there are no more) and attends
over those alone.

Three labels reach the device trace (``jax.named_scope``; the kernels carry
their own names): ``dsa_index`` (the indexer's projections, norm, rotary,
pool write and scores), ``dsa_select`` (the selection) and ``dsa_attend``
(whatever gathers, masks and attends over the selected keys; the one-token
rows' part under ``dsa_rows`` inside it).

Two routes, by the chunk's length as everywhere on this path, through the
same two kernels (``ops/sparse_index.py``: the scores, the exact selection):

* a chunk of two tokens or more, cut into atoms: a tile of the scores
  kernel and of the selection kernel is an atom, its rows at consecutive
  positions, and the ragged paged kernel runs under the selection's MASK,
  over either kind of pool: it visits every cached pair of the atom and
  keeps the selected (a prefill that reads the selected rows only is not
  written);
* a one-token chunk (every row of ``decode_forward``): the scores kernel
  takes each row as a tile of its own (thousands of keys a grid step:
  ``sparse_index.score_keys``), the selection kernel all of them as ONE
  tile whose rows stand each at its own sequence's last position and which
  walks up to the longest's; the mask's set is read out as positions
  (``sparse_index.positions_from_mask``: no sort, no scatter), the selected
  rows of the pool are GATHERED through the block table (K and V a KV head,
  or ONE latent row that serves every head: absorbed attention, the value
  its leading lanes), and softmax attention runs over those ``index_topk``
  rows: what it reads of the pool does not grow with the context.

An engine whose attention takes no atoms (``prefill_attn`` ``xla`` or
``flash``: the CPU's, the tests') runs every row through the exact
``jax.numpy`` twins, a tile of one row each.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ...models.layers import apply_rope, layer_norm
from ...ops import sparse_index

NEG_INF = jnp.finfo(jnp.float32).min


def index_scale(cfg) -> float:
    return float(cfg.index_heads * cfg.index_head_dim) ** -0.5


def index_rows(p, y, cfg, positions):
    """The indexer's part of flat tokens: ``(qI [n, Hi, Di], kI [n, Di], w
    [n, Hi] float32)``, qI and kI rotated over their leading
    ``cfg.index_rope_dim`` dims (0: their whole width) at the model's
    ``rope_theta``. ``y``: the layer's normed rows [n, D], which key and
    weights read and, without ``cfg.index_q_latent``, the queries too; with
    it the pair ``(y, c_q)``, c_q [n, q_lora_rank] latent attention's normed
    query latent (``model._mla_query_latent`` makes it), which the queries
    read."""
    y, q_in = y if cfg.index_q_latent else (y, y)
    n, hi, di = y.shape[0], cfg.index_heads, cfg.index_head_dim
    rot = lambda t: apply_rope(  # noqa: E731
        t[None], positions[None], cfg.rope_theta,
        rotary_dim=cfg.index_rope_dim or None)[0]
    # (w_qi lies [out, in]: model.serving_layout)
    q_i = rot(jnp.einsum("tc,qc->tq", q_in, p["w_qi"]).reshape(n, hi, di))
    k_i = rot(layer_norm(y @ p["w_ki"], p["ki_norm"]["scale"],
                         p["ki_norm"]["bias"], cfg.rms_norm_eps)[:, None])
    w = jnp.einsum("td,dh->th", y, p["w_w"],
                   preferred_element_type=jnp.float32)
    return q_i, k_i[:, 0], w


def pair_mates(token_seq, token_pos, live):
    """[n] int32: for each flat row the row of the SAME batch that holds its
    sequence's token at position ``pos ^ 1`` (the other slot of its row in
    the indexer's pool), -1 where the batch has none. A chunk's tokens are
    consecutive rows, so the mate is a neighbour or absent."""
    n = token_pos.shape[0]
    j = jnp.clip(jnp.arange(n) + jnp.where(token_pos % 2 == 0, 1, -1),
                 0, n - 1)
    found = live & live[j] & (token_seq[j] == token_seq) \
        & (token_pos[j] == (token_pos ^ 1))
    return jnp.where(found, j, -1)


def index_pool_write(pool, layer, dest, k_i, mates):
    """The new tokens' indexer keys k_i [n, Di] into layer ``layer`` of the
    pool [L, slots / 2, 2 x Di] at flat slots ``dest`` [n] (out of range =
    dropped): slot s is the lanes of its parity in row s // 2. A row is
    written WHOLE: the other slot's lanes are its key in this batch
    (``mates``: :func:`pair_mates`; both rows of a pair then write the same
    row) or what the pool held."""
    di = k_i.shape[1]
    row = dest // 2                    # the drop sentinel stays out of range
    odd = (dest % 2 == 1)[:, None]
    held = pool[layer, jnp.minimum(row, pool.shape[1] - 1)]       # [n, 2Di]
    other = jnp.where((mates >= 0)[:, None], k_i[jnp.maximum(mates, 0)],
                      jnp.where(odd, held[:, :di], held[:, di:])
                      .astype(k_i.dtype))
    rows = jnp.where(odd, jnp.concatenate([other, k_i], axis=1),
                     jnp.concatenate([k_i, other], axis=1))
    return pool.at[layer, row].set(rows.astype(pool.dtype), mode="drop")


def seq_index_keys(idx_pool, layer, block_tables, block_size: int):
    """[S, C, Di]: every sequence slot's cached indexer keys of ``layer`` in
    position order, a BLOCK at a time through its table (C = the table's
    blocks x ``block_size``; what lies past a sequence's length is whatever
    the blocks hold, and no row may see it)."""
    width = idx_pool.shape[-1]
    # whole blocks by (layer, block), as K and V's rows are gathered: the
    # pool seen as [L, blocks, rows of a block, 2Di] (its rows regrouped,
    # nothing moved)
    blocks = idx_pool.reshape(idx_pool.shape[0], -1, block_size // 2, width)
    keys = blocks[layer, block_tables]               # [S, Bps, bs / 2, 2Di]
    return keys.reshape(block_tables.shape[0], -1, width // 2)


def kernel_impl(name: str) -> str:
    """The paged kernels' ``impl`` word for a ``prefill_attn`` entry's name
    (the entries that take no atoms run the ``jax.numpy`` twins)."""
    return {"kernel": "pallas",
            "kernel_interpret": "pallas_interpret"}.get(name, "xla")


def rows_walk(impl: str, cfg, c: int, itemsize: int):
    """``(keys a scores step, keys a selection chunk)`` of the one-token
    rows' route under ``impl`` over a table of ``c`` keys (the pool's
    ``itemsize``): the kernels' own steps, by the shape of a one-row tile
    (no more than the table: what they pad it by is not its keys); the
    ``jax.numpy`` twins score and select over the whole table."""
    if impl == "xla":
        return c, c
    return (min(c, sparse_index.score_keys(
        1, cfg.index_heads, cfg.index_head_dim, itemsize, c)),
            min(c, sparse_index.select_chunk(c)))


def pool_views(cfg, pools):
    """``(k_cache, v_cache, v_dim, idx)`` of a layer's ``pools``: (k, v, idx)
    of a K-and-V pool; (latent, idx) of a latent one, whose value is its
    rows' leading ``kv_lora_rank`` lanes."""
    if cfg.kv_lora_rank:
        return pools[0], None, cfg.kv_lora_rank, pools[1]
    return pools[0], pools[1], None, pools[2]


def attend_atoms(q, q_i, w, k_seq, k_cache, v_cache, layer, ctx, cfg, impl,
                 v_dim=None):
    """The chunks of two tokens or more: per atom the scores, the selection
    and the ragged kernel under its mask. -> [T, H, D] ([.., ``v_dim``]
    over a latent pool) in packed rows (the one-token and padding rows
    gather the reserved dead atom's zeros)."""
    from ...ops.paged_attention import ragged_prefill_attention

    s = ctx.block_tables.shape[0]
    tile_seq = jnp.clip(ctx.token_seq[ctx.atom_qidx[:, 0]], 0, s - 1)
    tile_hi = jnp.where(ctx.atom_qlen > 0, ctx.atom_pos0 + ctx.atom_qlen, 0)
    with jax.named_scope("dsa_index"):
        scores = sparse_index.index_scores(
            q_i[ctx.atom_qidx], w[ctx.atom_qidx], k_seq, tile_seq, tile_hi,
            scale=index_scale(cfg), impl=impl)
    with jax.named_scope("dsa_select"):
        sel = sparse_index.select_topk(scores, ctx.atom_pos0, ctx.atom_qlen,
                                       k=cfg.index_topk, impl=impl)
    with jax.named_scope("dsa_attend"):
        out_at = ragged_prefill_attention(
            q[ctx.atom_qidx], k_cache, v_cache, ctx.atom_tables,
            ctx.atom_pos0, ctx.atom_qlen, block_size=ctx.block_size,
            layer=layer, impl=impl, sel=sel, v_dim=v_dim)
        return out_at.reshape(-1, *out_at.shape[2:])[ctx.atom_inv]


def attend_rows(q, q_i, w, k_seq, k_cache, v_cache, layer, block_tables,
                seq_lens, block_size: int, cfg, impl: str, v_dim=None):
    """One-token rows, one a sequence slot: q [S, H, D], qI [S, Hi, Di], w
    [S, Hi], ``seq_lens`` [S] the slot's length WITH the row's token (0: no
    row). The scores of each row as a tile of its own, the exact
    ``index_topk`` best of all rows as one tile (row s at position
    ``seq_lens[s] - 1``), their positions out of the mask, and attention
    over the gathered rows of K and V, or of a latent pool (``v_cache``
    None: ONE gathered row serves all H heads, its leading ``v_dim`` lanes
    the value; the products in the pool's dtype, as the paged kernels feed
    them). -> [S, H, D] ([S, H, ``v_dim``])."""
    s, h, d = q.shape
    c = k_seq.shape[1]
    k = min(cfg.index_topk, c)
    with jax.named_scope("dsa_index"):
        scores = sparse_index.index_scores(
            q_i[:, None], w[:, None], k_seq, jnp.arange(s), seq_lens,
            scale=index_scale(cfg), impl=impl)               # [S, 1, C]
    with jax.named_scope("dsa_select"):
        sel = sparse_index.select_topk(
            scores.reshape(1, s, c), (seq_lens - 1)[None],
            jnp.full((1,), s, jnp.int32), k=k, impl=impl)
        top = sparse_index.positions_from_mask(sel[0], k=k)  # [S, k]; C: none
    with jax.named_scope("dsa_attend"), jax.named_scope("dsa_rows"):
        live = top < seq_lens[:, None]
        block = jnp.take_along_axis(
            block_tables, jnp.minimum(top, c - 1) // block_size, axis=1)
        slots = jnp.where(live, block * block_size + top % block_size, 0)
        if v_cache is None:
            rows = k_cache[layer, slots]                     # [S, k, D]
            logits = jnp.einsum("shd,scd->shc", q, rows,
                                preferred_element_type=jnp.float32) \
                / np.sqrt(d)
            logits = jnp.where(live[:, None, :], logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("shc,scv->shv", probs.astype(rows.dtype),
                             rows[..., :v_dim],
                             preferred_element_type=jnp.float32)
            out = jnp.where((seq_lens > 0)[:, None, None], out, 0.0)
            return out.astype(q.dtype)
        kvh = k_cache.shape[-2]
        k_sel = k_cache[layer, slots].astype(jnp.float32)    # [S, k, KVH, D]
        v_sel = v_cache[layer, slots].astype(jnp.float32)
        q_g = q.astype(jnp.float32).reshape(s, kvh, h // kvh, d)
        logits = jnp.einsum("sngd,scnd->sngc", q_g, k_sel) / np.sqrt(d)
        logits = jnp.where(live[:, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("sngc,scnd->sngd", probs, v_sel)
        out = jnp.where((seq_lens > 0)[:, None, None, None], out, 0.0)
        return out.reshape(s, h, d).astype(q.dtype)


def attend_tokens(q, q_i, w, k_seq, ctx, cfg):
    """Every packed row on its own, through the exact ``jax.numpy`` twins
    (a tile of one row): the route of an attention that takes no atoms."""
    from .model import _layer_kv, _paged_attention

    t = q.shape[0]
    s = ctx.block_tables.shape[0]
    with jax.named_scope("dsa_index"):
        scores = sparse_index.index_scores_reference(
            q_i[:, None], w[:, None], k_seq,
            jnp.minimum(ctx.token_seq, s - 1), scale=index_scale(cfg))
    with jax.named_scope("dsa_select"):
        sel = sparse_index.select_topk_reference(
            scores, ctx.token_pos, jnp.ones((t,), jnp.int32),
            k=cfg.index_topk)[:, 0]
    with jax.named_scope("dsa_attend"):
        return _paged_attention(
            q, *_layer_kv(ctx), ctx.token_seq, ctx.token_pos,
            ctx.block_tables, ctx.block_size, sel=sel)


def ragged_attend(q, q_i, w, pools, layer, ctx, cfg, impl_name: str):
    """Attention of one ``ragged_forward`` layer over the selected keys: q
    [T, H, D] (lane-padded as the pool is), the indexer's rows, the pools
    AFTER this layer's write, ``ctx`` a ``PrefillAttnContext`` (its K, V
    and ``v_dim`` are the pools'). -> [T, H, D] ([.., ``v_dim``] over a
    latent pool)."""
    k_cache, v_cache, v_dim, idx = pool_views(cfg, pools)
    with jax.named_scope("dsa_index"):
        k_seq = seq_index_keys(idx, layer, ctx.block_tables, ctx.block_size)
    if ctx.atom_qidx is None or kernel_impl(impl_name) == "xla":
        return attend_tokens(q, q_i, w, k_seq, ctx, cfg)
    out = attend_atoms(q, q_i, w, k_seq, k_cache, v_cache, layer, ctx, cfg,
                       kernel_impl(impl_name), v_dim)
    out_dec = attend_rows(
        q[ctx.dec_row], q_i[ctx.dec_row], w[ctx.dec_row], k_seq, k_cache,
        v_cache, layer, ctx.block_tables, ctx.dec_len, ctx.block_size, cfg,
        kernel_impl(impl_name), v_dim)
    # a slot with no one-token chunk scatters out of range (dropped)
    rows = jnp.where(ctx.dec_len > 0, ctx.dec_row, q.shape[0])
    return out.at[rows].set(out_dec, mode="drop")


def decode_attend(q, q_i, w, pools, layer, block_tables, seq_lens,
                  block_size: int, cfg, impl: str):
    """Attention of one ``decode_forward`` layer: every row a one-token
    row. ``impl``: the ``decode_attn`` entry's name, the kernels' word as it
    stands."""
    k_cache, v_cache, v_dim, idx = pool_views(cfg, pools)
    with jax.named_scope("dsa_index"):
        k_seq = seq_index_keys(idx, layer, block_tables, block_size)
    return attend_rows(q, q_i, w, k_seq, k_cache, v_cache, layer,
                       block_tables, seq_lens, block_size, cfg, impl, v_dim)
