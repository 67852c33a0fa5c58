"""``model_type: KeyeVL2`` (Kwai-Keye/Keye-VL-2.0-30B-A3B), the LANGUAGE model
— the equations of ISSUE 45 in plain ``jax.numpy``, for ONE CHIP'S SHARE of
an expert-parallel deployment: the router is as wide as published, the chip
holds some of its experts, and what the others would have added is left out
(``benchmark/README.md``, "What ``reduced`` may hold"). Points the published
``config.json`` does not pin are marked (A) and listed under ``assumed`` in
the configuration's file; ``sa_config`` maps key for key on the public
DeepSeek-V3.2 sparse-attention recipe.

Every layer alike, pre-norm: ``x += attn(rms(x))``, ``x += moe(rms(x))``,
eps ``rms_norm_eps``, untied head. With ``y_t`` the normed row of token t
and ``s <= t`` the positions of its sequence:

1. ``q_t = rope(rms_h(W_q y_t))`` [H, D], ``k_t = rope(rms_h(W_k y_t))``
   [KVH, D], ``v_t = W_v y_t``; ``rms_h`` an RMSNorm over each head's D with
   one learned [D] scale for q and one for k (A); rotary over all D,
   split-half, written as M-RoPE: ``mrope_section`` gives each of the D / 2
   frequencies one of THREE position rows (time, height, width). A text
   token's three rows are equal and the rotation is then plain rotary.
2. The indexer (A: it reads the row q reads): ``qI_t = rope(W_qI y_t)``
   [Hi, Di], ``kI_t = rope(LN(W_kI y_t))`` [Di] (LayerNorm with scale and
   bias), ``w_t = W_w y_t`` [Hi]; plain rotary over all Di by the token's
   time row; ``I(t, s) = (Hi x Di)^-1/2 x sum_j w_t[j] relu(qI_t[j] .
   kI_s)``.
3. ``S_t`` = the ``topk`` positions ``s <= t`` with the largest ``I(t, s)``,
   ties to the lower ``s`` (``lax.top_k``'s rule), all of them while ``t + 1
   <= topk``. Exact.
4. ``o_t[h] = softmax_{s in S_t}(q_t[h] . k_s[g(h)] / sqrt(D)) v_s[g(h)]``,
   ``g(h) = h // (H / KVH)``; ``attn = W_o concat_h o_t[h]``.
5. Experts: ``p = softmax(W_r y)`` over all ``num_experts`` in float32, the
   top ``num_experts_per_tok``, renormalised to sum 1 (``norm_topk_prob``),
   each a SwiGLU of ``moe_intermediate_size``, no shared expert; the chip
   adds ``g_e expert_e(y)`` for the chosen experts it HOLDS (ids
   ``first_expert_held`` onward, as many as the tree's leaves have).

Plain, and independent of ``parallel/moe.py``, ``ops/`` and ``inference/``:
no cache, no kernel; scores, selection and softmax a block of ``Q_BLOCK``
query rows at a time, the experts one at a time, so that a 16 k prompt fits
beside a serving engine and 49 k beside nothing else."""
import math

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import reference as ref

Q_BLOCK = 64        # queries a block of the reference's attention takes


def arch(hf):
    sa = hf["sa_config"]
    held = hf["num_experts"]
    cut = hf.get("reduced", {}).get("num_experts")
    return {"hidden_size": hf["hidden_size"],
            # ONE routed expert's width (what moe_roofline's expert_work
            # reads); intermediate_size is a dense MLP's that no layer has
            "intermediate_size": hf["moe_intermediate_size"],
            "num_layers": hf["num_hidden_layers"], "num_dense_layers": 0,
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "rotary_dim": hf["head_dim"],
            "mrope_section": tuple(hf["rope_scaling"]["mrope_section"]),
            "rope_theta": float(hf["rope_theta"]),
            "sliding_window": None,
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"],
            "index_topk": sa["topk"],
            # the ROUTER's width is the published count; the key itself
            # counts the experts held here (ids 0 onward)
            "num_experts": cut["published"] if cut else held,
            "experts_held": held, "first_expert_held": 0,
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "norm_topk_prob": hf["norm_topk_prob"]}


def program_widths(hf):
    a = arch(hf)
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "moe_intermediate_size": hf["moe_intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "num_heads": a["num_heads"], "num_kv_heads": a["num_kv_heads"],
            "head_dim": a["head_dim"], "vocab_size": hf["vocab_size"],
            "rope_theta": a["rope_theta"], "rms_norm_eps": a["norm_eps"],
            "qk_head_norm": True, "sliding_window": None,
            "index_topk": a["index_topk"], "index_heads": a["index_heads"],
            "index_head_dim": a["index_head_dim"],
            "num_experts": a["num_experts"],
            "experts_held": a["experts_held"],
            "first_expert_held": a["first_expert_held"],
            "num_experts_per_tok": a["num_experts_per_tok"],
            "norm_topk_prob": a["norm_topk_prob"],
            "n_shared_experts": 0, "scoring_func": "softmax",
            "topk_method": "greedy"}


# ------------------------------------------------------------------ rotary
def text_positions(s):
    """[3, S]: a text token's time, height and width rows, all its index."""
    return jnp.broadcast_to(jnp.arange(s), (3, s))


def mrope(a, x, pos3):
    """x [S, H, D] rotated in split-half pairs, frequency f of the D / 2 by
    the position row its ``mrope_section`` names: the first ``section[0]``
    by the time row, the next ``section[1]`` by height, the rest by width."""
    d = x.shape[-1]
    inv = 1.0 / (a["rope_theta"] ** (jnp.arange(0, d, 2) / d))    # [D/2]
    row_of = jnp.repeat(jnp.arange(3), jnp.asarray(a["mrope_section"]),
                        total_repeat_length=d // 2)
    ang = pos3[row_of, :].T.astype(jnp.float32) * inv             # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def plain_rope(a, x, positions):
    """x [S, H, D'] rotated over its whole width at the model's theta."""
    return ref.rope({"rotary_dim": x.shape[-1],
                     "rope_theta": a["rope_theta"]}, x, positions)


# --------------------------------------------------------------- attention
def _rel_gap(top, k):
    """Relative gap between the k-th and the (k+1)-th of sorted ``top`` (0
    where both are 0: an indexer score is exactly 0 where no head's product
    is positive, and a tie of zeros goes to the lower position in the
    program and here alike)."""
    return (top[:, k - 1] - top[:, k]) \
        / jnp.maximum(jnp.abs(top[:, k - 1]), 1e-30)


def index_rows(a, p, y, pos3):
    """The indexer's queries [S, Hi, Di], keys [S, Di] and head weights
    [S, Hi] of the normed rows y."""
    s, hi, di = y.shape[0], a["index_heads"], a["index_head_dim"]
    q_i = plain_rope(a, (y @ p["w_qi"]).reshape(s, hi, di), pos3[0])
    k_i = plain_rope(a, ref.layer_norm(p["ki_norm"], y @ p["w_ki"],
                                       a["norm_eps"])[:, None], pos3[0])
    return q_i, k_i[:, 0], y @ p["w_w"]


def attention(a, p, y, pos3, select="indexer"):
    """Sparse attention over one sequence's normed rows y [S, d] -> (out
    [S, d], the indexer's near-tie gaps [S]: a row's relative gap between
    its ``topk``-th and next score, 1.0 where it sees no more than
    ``topk``). ``select``: ``"indexer"``, or ``"all"`` for dense attention
    (what a test holds the short-context case against)."""
    s = y.shape[0]
    h, hk, d = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    topk, eps = a["index_topk"], a["norm_eps"]
    heads = lambda t, n: t.reshape(s, n, d)  # noqa: E731
    q = mrope(a, ref.rms_norm(p["q_norm"], heads(y @ p["wq"], h), eps), pos3)
    k = mrope(a, ref.rms_norm(p["k_norm"], heads(y @ p["wk"], hk), eps), pos3)
    v = heads(y @ p["wv"], hk)
    q_i, k_i, w = index_rows(a, p, y, pos3)
    scale = (a["index_heads"] * a["index_head_dim"]) ** -0.5
    pos = jnp.arange(s)
    sparse = select == "indexer" and s > topk

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        at = jnp.minimum(rows, s - 1)
        seen = rows[:, None] >= pos[None, :]                  # [Q, S]
        gap = jnp.ones((Q_BLOCK,))
        if sparse:
            each = jnp.einsum("qhd,sd->qhs", q_i[at], k_i)
            score = (jnp.maximum(each, 0.0) * w[at][..., None]).sum(1) * scale
            top, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf),
                                     topk + 1)
            gap = jnp.where(rows >= topk, _rel_gap(top, topk), 1.0)
            chosen = jnp.zeros((Q_BLOCK, s), bool).at[
                jnp.arange(Q_BLOCK)[:, None], idx[:, :topk]].set(True)
            seen = jnp.logical_and(seen, chosen)
        qb = q[at].reshape(Q_BLOCK, hk, h // hk, d)
        logits = jnp.einsum("qngd,snd->qngs", qb, k) / math.sqrt(d)
        logits = jnp.where(seen[:, None, None, :], logits, -jnp.inf)
        e = jnp.exp(logits - logits.max(-1, keepdims=True))
        out = jnp.einsum("qngs,snd->qngd", e / e.sum(-1, keepdims=True), v)
        return out.reshape(Q_BLOCK, h * d), gap

    n_blocks = -(-s // Q_BLOCK)
    out, gaps = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    return (out.reshape(n_blocks * Q_BLOCK, -1)[:s] @ p["wo"],
            gaps.reshape(-1)[:s])


# --------------------------------------------------------------------- MLP
def router(a, w_r, y):
    """Gates [S, E] over the router's WHOLE width (a token's renormalised
    weights at its chosen experts, 0 elsewhere) and the token's relative
    near-tie gap between its k-th and (k+1)-th probability."""
    k = a["num_experts_per_tok"]
    probs = jax.nn.softmax(y @ w_r, axis=-1)
    top, idx = jax.lax.top_k(probs, k + 1)
    gap = _rel_gap(top, k)
    top = top[:, :k]
    if a["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(idx[:, :k], a["num_experts"])
             * top[..., None]).sum(1)
    return gates, gap


def experts(a, p, y):
    """The held experts' part of the layer: every held expert over every
    token, one at a time, its gate zeroing the tokens that did not choose
    it; a choice of an expert that is not here adds nothing."""
    gates, gap = router(a, p["router"], y)
    first, held = a["first_expert_held"], p["w_up"].shape[0]

    def one(acc, e):
        w_gate, w_up, w_down, g = e
        out = ref.swiglu({"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                         y)
        return acc + g[:, None] * out, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["w_gate"], p["w_up"], p["w_down"],
        gates[:, first:first + held].T))
    return out, gap


def block(a, p, x, pos3=None, select="indexer"):
    """-> (x, (the router's gaps [S], the indexer's gaps [S]))."""
    norm = lambda q, t: ref.rms_norm(q, t, a["norm_eps"])  # noqa: E731
    pos3 = text_positions(x.shape[0]) if pos3 is None else pos3
    out, index_gap = attention(a, p["attn"], norm(p["attn_norm"], x), pos3,
                               select)
    x = x + out
    out, router_gap = experts(a, p["moe"], norm(p["mlp_norm"], x))
    return x + out, (router_gap, index_gap)


def sequence_logits(a, params, ids):
    return ref.decoder_logits(
        params, ids, lambda p, x: block(a, p, x)[0],
        lambda p, x: ref.rms_norm(p, x, a["norm_eps"]))


def _gaps(a, params, ids):
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"][ids])
        _, gaps = jax.lax.scan(lambda x, p: block(a, f32(p), x), x,
                               params["layers"])
    return gaps


def router_gaps(a, params, ids):
    """[L, S]: per layer and position, :func:`router`'s relative gap between
    the k-th and (k+1)-th probability in THIS forward (float32, highest)."""
    return _gaps(a, params, ids)[0]


def index_gaps(a, params, ids):
    """[L, S]: per layer and position, the relative gap between the row's
    ``topk``-th and (``topk`` + 1)-th indexer score where it sees more than
    ``topk`` positions (1.0 where it does not): within the served
    precision's rounding the served SELECTION may differ there, and the
    outputs legitimately with it."""
    return _gaps(a, params, ids)[1]


# ------------------------------------------------------------------- counts
def matmul_params(a):
    """Weights a token meets in a matrix product: attention and the indexer,
    its OWN ``num_experts_per_tok`` experts, the router and the head."""
    d = a["hidden_size"]
    index = d * (a["index_heads"] * a["index_head_dim"]
                 + a["index_head_dim"] + a["index_heads"])
    mlp = a["num_experts_per_tok"] * 3 * d * a["intermediate_size"] \
        + d * a["num_experts"]
    return a["num_layers"] * (flops.attention_params(a) + index + mlp) \
        + d * a["vocab_size"]


def train_flops_per_token(a, seq):
    """6 a matmul weight, the attention's two products over the SELECTED
    pairs (a window of ``topk`` counts as many) and the indexer's scores
    over all causal pairs, forward and backward."""
    selected = flops.attention_train_flops(
        {**a, "sliding_window": a["index_topk"]}, seq)
    scored = 3 * 2 * a["index_heads"] * a["index_head_dim"] \
        * a["num_layers"] * flops.causal_pairs(seq) / seq
    return 6 * matmul_params(a) + selected + scored


# what the rooflines of the selection count (``layer_kinds`` below)
def selected_attention_work(a, sel_pairs, ctx_tokens, itemsize=2):
    """``(FLOPs, bytes)`` the attention over atoms needs in all layers for
    ``sel_pairs`` (row, selected token) pairs: both products of every head,
    and the chunks' ``ctx_tokens`` of context read once as K and V."""
    per_pair = a["num_heads"] * 4 * a["head_dim"]
    row = 2 * a["num_kv_heads"] * a["head_dim"] * itemsize
    return (a["num_layers"] * sel_pairs * per_pair,
            a["num_layers"] * ctx_tokens * row)


def selected_rows_bytes(a, dec_sel_tokens, itemsize=2):
    """Bytes the one-token rows' attention reads in all layers: K and V of
    every selected token."""
    return a["num_layers"] * dec_sel_tokens \
        * 2 * a["num_kv_heads"] * a["head_dim"] * itemsize


def index_work(a, attn_pairs, dec_ctx_tokens, itemsize=2):
    """``(FLOPs, bytes)`` of the indexer's scores in all layers: the
    chunks' (row, cached token) pairs at ``heads x dim x 2`` each, and the
    one-token rows' whole contexts read as indexer keys."""
    return (a["num_layers"] * attn_pairs
            * a["index_heads"] * a["index_head_dim"] * 2,
            a["num_layers"] * dec_ctx_tokens * a["index_head_dim"] * itemsize)


# ------------------------------ the kind of layer it has: a selection
# (``benchmark.reference.layer_kind``; the loops' conditions below stood in
# ``metrics/dsa_{share_pct,index_roofline,prefill_roofline,decode_roofline}
# .py`` until PR 62)
SCOPES = ("dsa_index", "dsa_select", "dsa_attend")
# the three Pallas kernels, by the names they carry
KERNELS = (("dsa_index_scores", "dsa_index"), ("dsa_select", "dsa_select"),
           ("dsa_prefill", "dsa_attend"))


def layer_kinds():
    """Every layer's attention reads keys a learned indexer SELECTED.
    ``dsa_index``: the indexer's projections, norm, rotary, pool write, the
    gather of a sequence's keys through its block table and the scores;
    ``dsa_select``: the selection; ``dsa_attend``: whatever gathers, masks
    and attends over the selected keys (``dsa_rows``, the one-token rows'
    part, inside it)."""
    return {"selection": {
        "scopes": SCOPES, "kernels": KERNELS,
        "roles": {"score": ("dsa_index",), "select": ("dsa_select",),
                  "attend": ("dsa_attend",)},
        "score": {"scopes": ("dsa_index",),
                  "kernels": (("dsa_index_scores", "dsa_index"),),
                  "work": score_work},
        "prefill": {"scopes": ("dsa_attend", "dsa_rows"),
                    "kernels": (("dsa_prefill", "dsa_attend"),),
                    "work": prefill_work},
        "rows": {"scopes": ("dsa_rows",), "kernels": (),
                 "work": rows_work}}}


def score_work(obs):
    """The indexer's floor, a traced round of either program: the one-token
    rows read their whole context as indexer keys (``dec_ctx_tokens`` of the
    ``round`` record x 128 B a token and layer) over the HBM bandwidth, PLUS
    the prompt chunks' scores' FLOPs (``attn_pairs`` x 16 heads x 64 x 2 a
    layer) through ``flops.roofline_seconds`` (``index_work``); against the
    time under ``dsa_index``."""
    a, peaks = arch(obs["config"]), obs["peaks"]

    def work(d, _counted, seconds):
        pairs, ctx = d.get("attn_pairs", 0), d.get("dec_ctx_tokens", 0)
        took = seconds.get("dsa_index")
        if not (pairs or ctx) or "sel_pairs" not in d or not took:
            return None
        ops_needed, bytes_needed = index_work(a, pairs, ctx)
        return (flops.roofline_seconds(ops_needed, 0, peaks)[0]
                + bytes_needed / peaks["hbm_bytes_per_s"], took)
    return work


def prefill_work(obs):
    """The attention over atoms' floor, a traced ``ragged_forward`` round:
    for every (row, SELECTED token) pair of the prompt chunks (``sel_pairs``
    of the ``round`` record) and every head both products, ``4 x head_dim``
    FLOPs, in each layer; and the chunk's context read once as K and V (the
    record has no per-chunk contexts, so their floor: a chunk of n <=
    ``max_tokens_per_batch`` rows that covers P pairs of ``attn_pairs``
    reads at least P / n rows): ``selected_attention_work`` through
    ``flops.roofline_seconds``; against the time under ``dsa_attend`` that
    is not the one-token rows' (``dsa_rows``)."""
    a, peaks = arch(obs["config"]), obs["peaks"]
    chunk = obs["engine"].config.max_tokens_per_batch

    def work(d, _counted, seconds):
        pairs, took = d.get("sel_pairs"), seconds.get("dsa_attend")
        if d["program"] != "ragged_forward" or not pairs or not took:
            return None
        return (flops.roofline_seconds(
            *selected_attention_work(a, pairs, d.get("attn_pairs", 0) / chunk),
            peaks)[0], took)
    return work


def rows_work(obs):
    """The one-token rows' floor, a traced round of EITHER program (every
    row of a ``decode_forward``, the one-token chunks of a mixed
    ``ragged_forward``): K and V of every SELECTED token (``dec_sel_tokens``
    of the ``round`` record; 2,048 B a token and layer at 4 kv heads of 128
    in bf16) over the HBM bandwidth (``selected_rows_bytes``); against the
    time under ``dsa_rows``: the gather of the selected rows through the
    block table and the attention over them."""
    a, peaks = arch(obs["config"]), obs["peaks"]

    def work(d, _counted, seconds):
        tokens, took = d.get("dec_sel_tokens"), seconds.get("dsa_rows")
        if not tokens or not took:
            return None
        return selected_rows_bytes(a, tokens) / peaks["hbm_bytes_per_s"], took
    return work
