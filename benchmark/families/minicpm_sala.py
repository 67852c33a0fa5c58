"""``model_type: minicpm_sala`` (openbmb/MiniCPM-SALA, 9B) — the equations of
ISSUE 59 in plain ``jax.numpy``. Points the published ``config.json`` does not
pin are marked (A) and listed under ``assumed`` in the configuration's file.

``num_hidden_layers`` layers, each a MIXER and then a dense SwiGLU MLP, every
sublayer ``x <- x + r f(RMSNorm(x))`` with ``r = scale_depth / sqrt(
mup_denominator)`` (the PUBLISHED depth's, whatever the cut); ``x_0 =
scale_emb embed(id)``; after the last, RMSNorm, division by ``hidden_size /
dim_model_base`` and an untied head. Layer ``l``'s mixer is ``mixer_types[l]``.
The PROGRAM walks the same stack as a pattern of two characters a layer
(``*F`` | ``LF``: ``program_widths``' ``layer_pattern``, ``num_layers`` = 2 x
``num_hidden_layers``); this file walks layers.

*Lightning attention* (``lightning-attn``; arXiv:2401.04658). H =
``lightning_nh`` heads of D = ``lightning_head_dim`` for query, key and value
alike (``lightning_nkv`` = H), no bias:

1. ``q, k, v = y W_q, y W_k, y W_v``; q and k RMS-normed a head with a learned
   [D] weight (``qk_norm``; A: a head at a time) and rotated over the whole
   head at ``rope_theta`` (``lightning_use_rope``); no activation on them (A);
2. ``S <- lambda_h S + k v^T``, ``o = S^T q / sqrt(D)`` (``lightning_scale``)
   from ``S = 0`` [H, D, D], ``lambda_h = exp(-2^(-8 (h + 1) / H))`` (A:
   Lightning Attention's slopes, no factor a layer): a ``lax.scan`` over
   TOKENS, one state, no chunks, no cache; no softmax, no normaliser;
3. ``o`` RMS-normed over all H x D values together (``use_output_norm``; A)
   times ``sigmoid(y W_z)`` (``use_output_gate``; A: a full projection); out
   ``o W_o``.

*Sparse attention* (``minicpm4``: InfLLM-v2, MiniCPM4's technical report
arXiv:2506.07900 under its ``sparse_config``'s sizes, A). H heads over KVH KV
heads of D; a GROUP is the H / KVH heads of one KV head; q and k RMS-normed a
head; NO positional term (``attn_use_rope`` false):

1. pooled keys ``c_j = mean(k_s : stride j <= s < stride j + kernel)`` a KV
   head, window ``j`` visible to the query at ``t`` once ``stride j + kernel -
   1 <= t``;
2. a query whose context ``t + 1`` is under ``dense_len`` reads every block
   (A: judged a ROW at its own context, so that a prompt's logits do not
   depend on how it is chunked; the published code judges a whole call);
3. else ``p_h(j) = softmax_j(q_h . c_j / sqrt(D))`` over the visible windows
   (A: ONE softmax over the strided windows; the published kernels reach the
   normaliser through a second, coarser pooling), ``P(j)`` its sum over the
   group's heads, ``B(b) = max P(j)`` over the windows that overlap block
   ``b`` = tokens ``[block b, block b + block)``; the query reads the first
   ``init_blocks`` blocks, the ``window_size / block`` ending with its own,
   and the best ``topk - 1`` of the rest by ``B`` (A: ``topk`` + the window's
   blocks in all, the first among them; ties to the lower block);
4. ``a = softmax(q k^T / sqrt(D)) v`` over the tokens ``s <= t`` of the blocks
   read, all heads of a group reading the same blocks; times ``sigmoid(y
   W_g)`` (``attn_use_output_gate``); out ``a W_o``.

Row by row from the whole sequence's keys, a block of ``Q_BLOCK`` query rows
at a time: no cache, no pages, no kernel, no chunk. What is a function of a
row alone (the projections, the gates, the MLP) runs ``ROW_BLOCK`` rows at a
time and the lightning recurrence ``HEAD_BLOCK`` heads at a time, so that 96 k
tokens of [S, 4096] float32 rows fit one chip beside the weights: the order
of the work, not another function.

The logits come back UNEMBEDDED ON DEMAND (:class:`Logits`): ``[S, 73448]``
float32 is 10.8 GB at the cell's shortest prompt, beside 9.85 GiB of program,
and the harness slices the rows it compares (``reference.greedy_margins``,
``parity``) outside its ``jit``. The object holds the normed, scaled hidden
rows and the head; ``logits[rows]`` is ``h[rows] @ W`` in float32 at the
highest precision, ``np.asarray(logits)`` all of it, a block of rows at a
time onto the host.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops
from benchmark import reference as ref

Q_BLOCK = 64        # queries a block of the reference's attention takes
ROW_BLOCK = 2048    # rows a block of the per-row work takes
HEAD_BLOCK = 4      # heads the lightning recurrence walks at a time
# (A) MiniCPM4's sparse_config (openbmb/MiniCPM4-8B's config.json): the
# catalog row gives "block top-64" only
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "init_blocks": 1, "window_size": 2048,
          "dense_len": 8192}
KINDS = {"minicpm4": "*", "lightning-attn": "L"}


def layer_pattern(hf):
    """The program's pattern: two characters a published layer."""
    return "".join(KINDS[m] + "F" for m in hf["mixer_types"])


def arch(hf):
    sp = {**SPARSE, **hf.get("sparse_config", {})}
    assert len(hf["mixer_types"]) == hf["num_hidden_layers"], hf
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            # published layers: each has a mixer AND an MLP
            "num_layers": hf["num_hidden_layers"],
            "mixers": tuple(KINDS[m] for m in hf["mixer_types"]),
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"],
            "lightning_heads": hf["lightning_nh"],
            "lightning_head_dim": hf["lightning_head_dim"],
            "rope_theta": float(hf["rope_theta"]),
            "rotary_dim": hf["lightning_head_dim"],
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            "embed_scale": float(hf["scale_emb"]),
            # the PUBLISHED depth's, whatever the cut
            "residual_scale": hf["scale_depth"]
            / math.sqrt(hf["mup_denominator"]),
            "logit_scale": hf["dim_model_base"] / hf["hidden_size"],
            "block": sp["block_size"], "kernel": sp["kernel_size"],
            "stride": sp["kernel_stride"], "init": sp["init_blocks"],
            "window": sp["window_size"] // sp["block_size"],
            # blocks a query reads: topk + the window's, the first among
            # the topk
            "blocks_read": sp["topk"] + sp["window_size"] // sp["block_size"],
            "dense_len": sp["dense_len"]}


def program_widths(hf):
    a = arch(hf)
    assert not hf["attn_use_rope"] and hf["lightning_use_rope"] \
        and hf["qk_norm"] and hf["use_output_gate"] \
        and hf["use_output_norm"] and hf["attn_use_output_gate"] \
        and hf["lightning_nkv"] == hf["lightning_nh"] \
        and not hf["attention_bias"], hf
    return {"hidden_size": hf["hidden_size"],
            # the program counts pattern characters, two a published layer
            "num_layers": 2 * hf["num_hidden_layers"],
            "layer_pattern": layer_pattern(hf),
            "intermediate_size": hf["intermediate_size"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "pos_embed": "none",
            "qk_head_norm": True, "attn_out_gate": True,
            "vocab_size": hf["vocab_size"],
            "rms_norm_eps": hf["rms_norm_eps"],
            "rope_theta": float(hf["rope_theta"]),
            "lightning_heads": a["lightning_heads"],
            "lightning_head_dim": a["lightning_head_dim"],
            "sparse_block_topk": a["blocks_read"],
            "sparse_block_size": a["block"],
            "sparse_block_kernel": a["kernel"],
            "sparse_block_stride": a["stride"],
            "sparse_block_init": a["init"],
            "sparse_block_window": a["window"],
            "sparse_block_dense_len": a["dense_len"],
            "embed_scale": a["embed_scale"],
            "residual_scale": a["residual_scale"],
            "logit_scale": a["logit_scale"],
            "activation": "silu", "mlp_type": "glu",
            "tie_embeddings": hf["tie_word_embeddings"]}


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _head_norm(scale, x, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * scale


def _by_rows(f, *xs, block=ROW_BLOCK):
    """``f(*rows)`` (a function of each row alone) over the arrays ``xs``
    [S, ...], ``block`` rows at a time."""
    s = xs[0].shape[0]
    if s <= block:
        return f(*xs)
    n = -(-s // block)
    cut = [jnp.pad(x, ((0, n * block - s),) + ((0, 0),) * (x.ndim - 1))
           .reshape(n, block, *x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda rows: f(*rows), tuple(cut))
    return out.reshape(n * block, *out.shape[2:])[:s]


@jax.tree_util.register_pytree_node_class
class Logits:
    """``[S, V]`` float32 logits held as the normed, scaled hidden rows ``h``
    [S, d] float32 and the head ``w`` [d, V] as stored: a row is unembedded
    when it is asked for (the module's docstring has why)."""

    def __init__(self, h, w):
        self.h, self.w = h, w

    def tree_flatten(self):
        return (self.h, self.w), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    shape = property(lambda self: (self.h.shape[0], self.w.shape[1]))
    dtype = jnp.dtype(jnp.float32)

    def __len__(self):
        return self.h.shape[0]

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return self.h[rows] @ self.w.astype(jnp.float32)

    def __array__(self, dtype=None, copy=None):
        out = np.concatenate([
            np.asarray(self[at:at + ROW_BLOCK])
            for at in range(0, len(self), ROW_BLOCK)])
        return out if dtype is None else out.astype(dtype)


# ------------------------------------------------------ lightning attention
def decay(heads):
    """[H]: a head's decay a token (A: Lightning Attention's slopes)."""
    return jnp.exp(-jnp.exp2(-8.0 * (jnp.arange(heads) + 1.0) / heads))


def lightning(a, p, x):
    """One lightning mixer over one sequence x [S, d] (already normed): the
    recurrence token by token from a zero state."""
    s, h, d = x.shape[0], a["lightning_heads"], a["lightning_head_dim"]
    pos = jnp.arange(s)
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h

    def some_heads(first):
        """The recurrence of ``hb`` heads from head ``first``: [S, hb, d]."""
        cols = lambda w: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, first * d, hb * d, axis=1)
        heads = lambda w: (x @ cols(w)).reshape(s, hb, d)     # noqa: E731
        q = ref.rope(a, _head_norm(p["q_norm"]["scale"], heads(p["wq"]),
                                   a["norm_eps"]), pos)
        k = ref.rope(a, _head_norm(p["k_norm"]["scale"], heads(p["wk"]),
                                   a["norm_eps"]), pos)
        lam = jax.lax.dynamic_slice_in_dim(decay(h), first, hb)[:, None, None]

        def token(state, inp):
            q_t, k_t, v_t = inp
            state = lam * state + k_t[:, :, None] * v_t[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, q_t) / math.sqrt(d)

        return jax.lax.scan(token, jnp.zeros((hb, d, d), jnp.float32),
                            (q, k, heads(p["wv"])))[1]

    o = jax.lax.map(some_heads, jnp.arange(0, h, hb))     # [h / hb, S, hb, d]
    o = jnp.moveaxis(o, 0, 1).reshape(s, h * d)

    def out(o_r, x_r):
        o_r = o_r / jnp.sqrt(jnp.square(o_r).mean(-1, keepdims=True)
                             + a["norm_eps"]) * p["o_norm"]["scale"]
        return (o_r * _sigmoid(x_r @ p["wz"])) @ p["wo"]

    return _by_rows(out, o, x)


# --------------------------------------------------------- sparse attention
def blocks_read(a, q, k, rows):
    """``(bool [KVH, Q, blocks], gaps [KVH, Q])``: the blocks the queries at
    positions ``rows`` [Q] read, a KV group each, from the whole sequence's
    normed keys k [S, KVH, D] and the rows' normed queries q [Q, H, D]; and
    the relative gap between the last block taken by score and the first
    left out (inf where the score decides nothing)."""
    s, kvh, d = k.shape
    bs, kern, stride = a["block"], a["kernel"], a["stride"]
    n_blocks = -(-s // bs)
    n_win = max((s - kern) // stride + 1, 0)
    own = rows // bs
    b = jnp.arange(n_blocks)
    seen = b[None, :] <= own[:, None]                             # [Q, B]
    forced = seen & ((b[None, :] < a["init"])
                     | (b[None, :] > own[:, None] - a["window"]))
    by_score = a["blocks_read"] - a["init"] - a["window"]
    no_gap = jnp.full((kvh, rows.shape[0]), jnp.inf)
    if n_win == 0 or by_score <= 0 or n_blocks <= by_score:
        return jnp.broadcast_to(seen[None], (kvh, *seen.shape)), no_gap
    at = jnp.arange(n_win)[:, None] * stride + jnp.arange(kern)[None, :]
    c = k[at].mean(1)                                          # [W, KVH, D]
    qg = q.reshape(q.shape[0], kvh, -1, d)
    logits = jnp.einsum("qkgd,wkd->kgqw", qg, c) / math.sqrt(d)
    visible = (jnp.arange(n_win) * stride + kern - 1)[None, :] \
        <= rows[:, None]                                          # [Q, W]
    logits = jnp.where(visible, logits, -jnp.inf)
    top = logits.max(-1, keepdims=True)
    e = jnp.where(visible, jnp.exp(logits - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(1)  # [K,Q,W]
    # a block's score: the maximum over the windows that overlap it, window
    # j = [stride j, stride j + kernel), block b = [bs b, bs b + bs)
    per, back = bs // stride, kern // stride - 1
    p = jnp.pad(p, ((0, 0), (0, 0), (back, n_blocks * per - n_win)))
    score = jax.lax.reduce_window(
        p, -jnp.inf, jax.lax.max, (1, 1, per + back), (1, 1, per), "VALID")
    rest = seen & ~forced
    score = jnp.where(rest[None], score, -jnp.inf)
    top, best = jax.lax.top_k(score, by_score + 1)           # ties: lower
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(kvh)[:, None, None],
        jnp.arange(rows.shape[0])[None, :, None],
        best[..., :by_score]].set(True)
    sparse = forced[None] | (chosen & rest[None])
    dense = (rows + 1 < a["dense_len"])[None, :, None]
    gaps = jnp.where(dense[..., 0] | ~jnp.isfinite(top[..., -1]), jnp.inf,
                     (top[..., -2] - top[..., -1]) / jnp.abs(top[..., -2]))
    return jnp.where(dense, seen[None], sparse), gaps


def sparse_attention(a, p, x):
    """One sparse-attention mixer over one sequence x [S, d] (already
    normed), ``Q_BLOCK`` query rows at a time against all S keys. -> ``(out
    [S, d], the selection's gaps [S, KVH])``."""
    s, h, kvh, d = (x.shape[0], a["num_heads"], a["num_kv_heads"],
                    a["head_dim"])
    pos = jnp.arange(s)
    k = _head_norm(p["k_norm"]["scale"], (x @ p["wk"]).reshape(s, kvh, d),
                   a["norm_eps"])
    v = (x @ p["wv"]).reshape(s, kvh, d)

    def block(start):
        rows = jnp.minimum(start + jnp.arange(Q_BLOCK), s - 1)
        qb = _head_norm(p["q_norm"]["scale"],
                        (x[rows] @ p["wq"]).reshape(Q_BLOCK, h, d),
                        a["norm_eps"])
        read, gaps = blocks_read(a, qb, k, rows)               # [KVH, Q, B]
        mask = read[:, :, pos // a["block"]] \
            & (pos[None, None, :] <= rows[None, :, None])      # [KVH, Q, S]
        scores = jnp.einsum("qkgd,skd->kgqs", qb.reshape(Q_BLOCK, kvh, -1, d),
                            k) / math.sqrt(d)
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        w = jnp.exp(scores - scores.max(-1, keepdims=True))
        out = jnp.einsum("kgqs,skd->qkgd", w / w.sum(-1, keepdims=True), v)
        return (out.reshape(Q_BLOCK, h * d)
                * _sigmoid(x[rows] @ p["w_g"])) @ p["wo"], gaps.T

    n_blocks = -(-s // Q_BLOCK)
    out, gaps = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    return (out.reshape(n_blocks * Q_BLOCK, -1)[:s],
            gaps.reshape(n_blocks * Q_BLOCK, -1)[:s])


# --------------------------------------------------------------------- walk
def _walk(a, params, ids):
    """Layer by layer, each kind of mixer reading the next layer of ITS
    stack, cast to float32 as it is used. -> ``(:class:`Logits` [S, V], the
    sparse layers' selection gaps [L_sparse, S, KVH])``."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    norm = lambda q, y: ref.rms_norm(q, y, a["norm_eps"])  # noqa: E731

    def at(tree, j, x):
        """Layer ``j`` of a stack in float32, cut out and cast only once the
        stream has reached it (the barrier orders both behind ``x``: twelve
        layers' slices and float32 copies at once are 20 GB)."""
        tree, x = jax.lax.optimization_barrier((tree, x))
        return f32(jax.tree_util.tree_map(lambda w: w[j], tree)), x

    r, seen, gaps = a["residual_scale"], {"*": 0, "L": 0}, []
    with jax.default_matmul_precision("highest"):
        x = a["embed_scale"] \
            * params["embed"]["embedding"][ids].astype(jnp.float32)
        for l, kind in enumerate(a["mixers"]):
            if kind == "*":
                p, x = at(params["attn_layers"], seen[kind], x)
                out, gap = sparse_attention(a, p["attn"],
                                            norm(p["attn_norm"], x))
                x = x + r * out
                gaps.append(gap)
            else:
                p, x = at(params["lightning_layers"], seen[kind], x)
                x = x + r * lightning(a, p, norm(p["norm"], x))
            seen[kind] += 1
            p, x = at(params["ffn_layers"], l, x)
            x = x + r * _by_rows(
                lambda y, p=p: ref.swiglu(p["mlp"], norm(p["mlp_norm"], y)),
                x)
        h = norm(f32(params["final_norm"]), x) * a["logit_scale"]
        return Logits(h, params["lm_head"]["kernel"]), jnp.stack(gaps)


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def selection_gaps(a, params, ids):
    """[L_sparse, S, KVH]: per sparse layer, position and KV group the
    relative gap between the last block taken by score and the first left
    out in THIS forward (float32, highest; inf where every block is read);
    ``tools/parity_probes.py`` counts those under the served precision's
    rounding, as ``parity`` counts a router's."""
    return _walk(a, params, ids)[1]


# ------------------------------------------------------- FLOPs and bytes
def layer_counts(a):
    """``(sparse-attention layers, lightning layers)``."""
    n = a["mixers"].count("*")
    return n, a["num_layers"] - n


def bsa_score_flops(a):
    """FLOPs of ONE (row, window) of the scores in one sparse layer: every
    query head's product with the window's pooled key."""
    return a["num_heads"] * a["head_dim"] * 2


def bsa_attend_flops(a):
    """FLOPs of ONE (row, KV group, attended token) in one sparse layer:
    the group's heads' two products."""
    return a["num_heads"] // a["num_kv_heads"] * a["head_dim"] * 4


def bsa_page_bytes(a, itemsize=2):
    """Bytes of ONE page a KV head: its keys and its values."""
    return a["block"] * a["head_dim"] * 2 * itemsize


def bsa_pool_bytes(a, itemsize=2):
    """Bytes of ONE pooled key (a window a KV head)."""
    return a["head_dim"] * itemsize


def la_step_flops(a):
    """FLOPs of the recurrence itself for ONE row in ONE lightning layer, by
    the sequential form, which no chunking undercuts: per element of the
    state [H, D, D] the write (2) and the read-out (2) (the decay's multiply
    is the third and not counted, as ISSUE 59 counts)."""
    return 4 * a["lightning_heads"] * a["lightning_head_dim"] ** 2


def la_row_bytes(a, itemsize=4):
    """Bytes of one row into and out of the recurrence in one layer: q, k, v
    in and o out, [H, D] float32 each."""
    return 4 * a["lightning_heads"] * a["lightning_head_dim"] * itemsize


def la_state_bytes(a, itemsize=4):
    """Bytes of ONE sequence's state in ONE lightning layer."""
    return a["lightning_heads"] * a["lightning_head_dim"] ** 2 * itemsize


def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers."""
    d = a["hidden_size"]
    n_sparse, n_light = layer_counts(a)
    q = a["num_heads"] * a["head_dim"]
    kv = a["num_kv_heads"] * a["head_dim"]
    dl = a["lightning_heads"] * a["lightning_head_dim"]
    return (n_sparse * (3 * d * q + 2 * d * kv) + n_light * 5 * d * dl
            + a["num_layers"] * 3 * d * a["intermediate_size"]
            + d * a["vocab_size"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met, attention's two products over the tokens a
    row reads in the sparse layers (every causal pair under ``dense_len``,
    ``blocks_read`` blocks past it), and three times the recurrence's own
    FLOPs in the lightning layers. The training path does not run this model;
    the count is the family's contract."""
    n_sparse, n_light = layer_counts(a)
    read = min((seq + 1) / 2, a["blocks_read"] * a["block"]) \
        if seq >= a["dense_len"] else (seq + 1) / 2
    attn = 3 * 4 * a["head_dim"] * a["num_heads"] * n_sparse * read
    return 6 * matmul_params(a) + attn + 3 * la_step_flops(a) * n_light


# ------------- the kinds of layer it has: a selection, and recurrent state
# (``benchmark.reference.layer_kind``; the counts and conditions below stood
# in ``metrics/{bsa,la}_*.py`` until PR 62)
BSA_SCOPES = ("bsa_pool", "bsa_score", "bsa_select", "bsa_attend",
              "bsa_rows")
# the custom calls: ``name=`` of the selection (ops/sparse_block.py), of the
# atoms' masked kernel and of the one-token rows' (inference/v2/bsa.py)
BSA_KERNELS = (("bsa_select", "bsa_select"), ("bsa_prefill", "bsa_attend"),
               ("bsa_rows", "bsa_rows"))
# lightning attention's state step runs through Mamba-2's entry
# (``ssm_state_step``, ``ops/ssm.py``; a program with ``L`` layers has no
# Mamba-2 layer beside them)
LA_KERNELS = (("ssm_state_step", "la_step"),)


def layer_kinds():
    """The ``*`` layers' attention reads blocks SELECTED from pooled keys:
    ``bsa_pool`` (the pooled keys' write) and ``bsa_score`` (their gather a
    sequence, the scores and the pooling to blocks) score, ``bsa_select``
    (the selection and the one-token rows' page tables) selects,
    ``bsa_attend`` (whatever gathers, masks and attends; ``bsa_rows``, the
    one-token rows' part, inside it) attends. The ``L`` layers' lightning
    attention is RECURRENT STATE: ``la_proj`` (the four projections in and
    ``wo`` out), ``la_gate`` (the head norms, the rotation, the output norm
    and gate) and ``la_scan`` (the recurrence: ``la_step``, the in-place
    state step, and ``la_chunk``, the chunked form's pieces, inside it)."""
    return {
        "selection": {
            "scopes": BSA_SCOPES, "kernels": BSA_KERNELS,
            "roles": {"score": ("bsa_pool", "bsa_score"),
                      "select": ("bsa_select",),
                      "attend": ("bsa_attend", "bsa_rows")},
            "score": {"scopes": ("bsa_score",), "kernels": BSA_KERNELS,
                      "work": score_work},
            "prefill": {"scopes": ("bsa_attend", "bsa_rows"),
                        "kernels": BSA_KERNELS, "work": prefill_work},
            "rows": {"scopes": ("bsa_score", "bsa_select", "bsa_rows"),
                     "kernels": BSA_KERNELS, "work": rows_work}},
        "recurrent_state": {
            "share_scopes": ("la_proj", "la_gate", "la_scan", "la_step",
                             "la_chunk"),
            "share_kernels": LA_KERNELS,
            "step_scopes": ("la_step",),
            "step_kernels": LA_KERNELS,
            "step_pieces": "la_pieces",
            "chunk_scopes": ("la_chunk",),
            "chunk_work": chunk_work}}


def block_scores_work(a, windows, visible_blocks):
    """``(FLOPs, bytes)`` of the scores of forwards whose rows saw
    ``windows`` windows and whose tiles ``visible_blocks`` blocks a KV
    head: every query head's product with every window's pooled key that
    its row may see, the pooled keys read once a tile (the windows a block x
    ``bsa_pool_bytes``) and a block score written a (row, KV head, visible
    block) in float32."""
    per_block = a["block"] // a["stride"]
    return (windows * bsa_score_flops(a),
            visible_blocks * per_block * bsa_pool_bytes(a)
            + windows // per_block * a["num_kv_heads"] * 4)


def score_work(obs):
    """The block scores' floor, a traced round of either program, from what
    the DEVICE counted of the forward (the following record's
    ``bsa_windows`` and ``bsa_visible_blocks``, summed over the sparse
    layers): ``block_scores_work`` through ``flops.roofline_seconds``;
    against the time under ``bsa_score``: the gather of a sequence's pooled
    keys through its block table, the products, the softmax over a row's
    windows, the sum over a group's heads and the pooling to blocks."""
    a, peaks = arch(obs["config"]), obs["peaks"]

    def work(_d, counted, seconds):
        if "bsa_pairs" not in counted or not seconds.get("bsa_score") \
                or not counted["bsa_windows"]:
            return None
        return (flops.roofline_seconds(
            *block_scores_work(a, counted["bsa_windows"],
                               counted["bsa_visible_blocks"]), peaks)[0],
                seconds["bsa_score"])
    return work


def prefill_work(obs):
    """The attention over atoms' floor, a traced ``ragged_forward`` round:
    for every (row, KV group, ATTENDED token) of the prompt chunks
    (``bsa_pairs`` less ``bsa_row_pairs`` of the following record: counted
    on the device from the selection itself, summed over the sparse layers)
    the group's heads' two products (``bsa_attend_flops``), and the pages
    the rows of an atom chose BETWEEN them read once a KV head (``bsa_pages``
    less ``bsa_row_pages`` x ``bsa_page_bytes``: the union is what the
    mathematics lets a tile share), through ``flops.roofline_seconds``;
    against the time under ``bsa_attend`` that is not the one-token rows'
    (``bsa_rows``). The count is the selection's own, the same whatever
    route attends."""
    a, peaks = arch(obs["config"]), obs["peaks"]

    def work(d, counted, seconds):
        if "bsa_pairs" not in counted:
            return None
        pairs = counted["bsa_pairs"] - counted.get("bsa_row_pairs", 0)
        pages = counted["bsa_pages"] - counted.get("bsa_row_pages", 0)
        if d["program"] != "ragged_forward" or pairs <= 0 \
                or not seconds.get("bsa_attend"):
            return None
        return (flops.roofline_seconds(
            pairs * bsa_attend_flops(a), pages * bsa_page_bytes(a),
            peaks)[0], seconds["bsa_attend"])
    return work


def rows_work(obs):
    """The one-token rows' floor, a traced ``decode_forward`` round: the
    pages a row selected as K and V a KV head (``bsa_pages`` of the
    following record x ``bsa_page_bytes``) and its context's pooled keys
    (``bsa_windows`` x KV heads x ``bsa_pool_bytes``: 32 B a token and layer
    where a dense row reads 1,024), over the HBM bandwidth; against the time
    under ``bsa_score``, ``bsa_select`` and ``bsa_rows``: everything between
    the pooled keys' write and the output (what a token indexer's ``rows``
    and ``score`` count between them)."""
    a, peaks = arch(obs["config"]), obs["peaks"]

    def work(d, counted, seconds):
        if "bsa_pairs" not in counted or d["program"] != "decode_forward" \
                or not counted["bsa_pages"] or not seconds:
            return None
        need = counted["bsa_pages"] * bsa_page_bytes(a) \
            + counted["bsa_windows"] * a["num_kv_heads"] * bsa_pool_bytes(a)
        return need / peaks["hbm_bytes_per_s"], sum(seconds.values())
    return work


def pieces_work(a, rows, pieces, first, layers):
    """``(FLOPs, bytes)`` of one forward's lightning pieces in all
    ``layers``, what no chunking can avoid: ``rows`` rows in ``pieces``
    pieces, ``first`` of them with no predecessor: the recurrence's own
    FLOPs by the SEQUENTIAL form (``la_step_flops`` a row and layer; a
    chunked form does more and reads lower), the rows in and out
    (``la_row_bytes``) and every piece's state (``la_state_bytes``), read
    where it has a predecessor and written always."""
    state = la_state_bytes(a)
    return (layers * rows * la_step_flops(a),
            layers * (rows * la_row_bytes(a)
                      + (2 * pieces - first) * state))


def chunk_work(obs):
    """``record -> (FLOPs, bytes)`` of a forward's lightning pieces (the
    pieces' rows are ``la_rows - decode_rows``, the pieces ``la_pieces``,
    summed over the layers, a one-token chunk one piece, less
    ``decode_rows`` x the layers, those that start a sequence ``la_first``;
    ``None`` where it carried none), or ``None`` for an engine without a
    state pool."""
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    if not stats or not stats.get("layers"):
        return None
    a = arch(obs["config"])
    layers = stats["layers"]

    def work(d):
        ones = d.get("decode_rows")
        if ones is None or "la_rows" not in d or "la_pieces" not in d:
            return None
        rows = d["la_rows"] - ones
        pieces = d["la_pieces"] // layers - ones
        if rows <= 0 or pieces <= 0:
            return None
        return pieces_work(a, rows, pieces, d.get("la_first", 0) // layers,
                           layers)
    return work
