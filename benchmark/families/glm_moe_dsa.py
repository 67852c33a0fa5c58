"""``model_type: glm_moe_dsa`` (zai-org/GLM-5) — the equations of ISSUE 65 in
plain ``jax.numpy``, for ONE CHIP'S SHARE of an expert-parallel deployment:
the router is as wide as published, the chip holds some of its experts, and
what the others would have added is left out (``benchmark/README.md``, "What
``reduced`` may hold"). Points the published ``config.json`` does not pin
are marked (A) and listed under ``assumed`` in the configuration's file.

Pre-norm residual blocks, RMSNorm eps ``rms_norm_eps``, no biases, untied
head. With ``y`` the normed rows of one sequence and ``s <= t`` its
positions:

*Latent attention* (MLA), in EXPANDED form and without a cache: ``c_q =
RMSNorm(y W_qa)``, ``q = c_q W_qb`` -> heads of (nope | rope); ``[c_kv | k_r]
= y W_kva``, ``c_kv = RMSNorm(c_kv)``; plain rotary (``rope_parameters``:
theta, no scaling) on q's rope part and on the ONE k_r all heads share;
``[k_nope | v]_h = c_kv W_kvb`` (``qk_nope_head_dim`` + ``v_head_dim`` a
head, which differ here: 192 and 256); ``score = (q_nope . k_nope + q_r .
k_r) * (nope + rope)^-1/2``; softmax over the SELECTED positions; out =
``[o_1 .. o_H] W_o``. Rotary pairs are split-half, as the program's weight
layout has them (A: a column permutation of the published interleaved
pairs, ``rope_interleave`` / ``indexer_rope_interleave``).

*The indexer* (DeepSeek-V3.2's, whose queries read the QUERY LATENT): ``qI =
c_q W_qI`` -> ``index_n_heads`` heads of ``index_head_dim``; ``kI = LN(y
W_kI)`` (A: LayerNorm with scale and bias, none on the queries); both rotated
over their FIRST ``qk_rope_head_dim`` dims (A: which of the 128); ``w = y
W_w``; ``I(t, s) = (heads x dim)^-1/2 sum_j w_t[j] relu(qI_t[j] . kI_s)`` (A:
the scale, which changes no selection). ``S_t`` = the ``index_topk``
positions ``s <= t`` with the largest ``I(t, s)``, ties to the lower ``s``
(``lax.top_k``'s rule), all of them while ``t + 1 <= index_topk``: by a dense
``[T, T]`` score a block of query rows at a time and a top-k. Exact.

*MLP.* Layers < ``first_k_dense_replace`` (the tree's ``dense_layers``): a
SwiGLU of ``intermediate_size``. The rest: ``s = sigmoid(y W_r)`` over ALL
``num_experts``; the top ``num_experts_per_tok`` of ``s + b`` (the
``noaux_tc`` bias, for the CHOICE only; ``n_group`` 1: no group limit);
weights ``s[chosen] / (sum + 1e-20) * routed_scaling_factor``; the chip adds
``w_e expert_e(y)`` for the chosen experts it HOLDS (ids
``first_expert_held`` onward, as many as the tree's leaves have) plus the
shared expert's SwiGLU of every token, which every chip computes alike.

Plain, and independent of ``parallel/moe.py``, ``ops/`` and ``inference/``:
no cache, no kernel, no absorbed form. The ORDER of the work is chosen so
that 24.6 k tokens fit one chip beside 10.4 GiB of weights, and is no other
function: a matrix stored in bf16 is not cast to float32 (``falcon_h1.matmul``:
the float32 rows as three bf16 terms that sum to them exactly, each product
native with float32 accumulation, what precision "highest" makes); what is
a function of a row alone runs ``ROW_BLOCK`` rows at a time; keys and values
are taken up through ``W_kvb`` ``HEAD_BLOCK`` heads at a time, each group's
output through its rows of ``W_o`` at once; the selection is kept as ``[S,
topk]`` positions; and of a long sequence's logits the LAST ``TAIL_ROWS`` rows
alone are unembedded (:class:`TailLogits`: ``[8700, 154880]`` float32 is
5.4 GB, and a ``falcon_h1.Logits`` that leaves a ``jit`` takes a COPY of the
1.77 GiB head out with it, beside an engine that leaves 2.5 GiB free; every
reader of the harness reads rows of the answer: ``reference.greedy_margins``
those before the emitted tokens, ``parity`` and ``dsa_faults`` the last 256).
The multi-token-prediction block (``num_nextn_predict_layers``) is no part of the
trunk's next-token logits and is not here."""
import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import reference as ref
from benchmark.families.falcon_h1 import DENSE_BYTES, matmul

Q_BLOCK = 64        # queries a block of the scores and the attention takes
HEAD_BLOCK = 8      # heads whose keys and values are held at once
ROW_BLOCK = 2048    # rows a function of a row alone takes at once
# the leaves :func:`matmul` takes as stored; every other is cast to float32
MATRICES = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo", "w_qi", "w_ki", "w_w",
            "w_gate", "w_up", "w_down")
# a latent row as the pool holds it: 512 + 64 values on 640 lanes
LANES = 128
# rows of a long sequence's logits that are unembedded: the answer's (the
# cell's answers are at most 512 tokens behind the prompt's last position)
TAIL_ROWS = 640


@jax.tree_util.register_pytree_node_class
class TailLogits:
    """``[S, V]`` float32 logits of which only the last rows are held,
    ``tail`` [K, V]: rows ``S - K`` onward (the module's docstring has why).
    ``logits[a:b]`` with both ends inside the tail gives those rows (inside
    a ``jit`` or outside); a row before it was never unembedded and raises."""

    def __init__(self, tail, length):
        self.tail, self.length = tail, length

    def tree_flatten(self):
        return (self.tail,), self.length

    @classmethod
    def tree_unflatten(cls, length, leaves):
        return cls(*leaves, length)

    shape = property(lambda self: (self.length, self.tail.shape[1]))
    dtype = jnp.dtype(jnp.float32)

    def __len__(self):
        return self.length

    def __getitem__(self, rows):
        if not isinstance(rows, slice) or rows.step not in (None, 1):
            raise IndexError(f"TailLogits takes a slice of rows, not {rows!r}")
        start, stop, _ = rows.indices(self.length)
        first = self.length - self.tail.shape[0]
        if start < first:
            raise IndexError(
                f"rows {start}:{stop} of {self.length}: only the last "
                f"{self.tail.shape[0]} rows of a long sequence's logits are "
                f"unembedded (TAIL_ROWS)")
        return self.tail[start - first:max(stop, start) - first]

    def __array__(self, dtype=None, copy=None):
        """The rows there are, the TAIL's: a reader that counts its rows
        from the end (``parity``: ``np.asarray(logits)[-n:]``) reads the
        rows it means; one that counts from the front must slice first."""
        import numpy as np

        out = np.asarray(self.tail)
        return out if dtype is None else out.astype(dtype)


def arch(hf):
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    held = hf["n_routed_experts"]
    cut = hf.get("reduced", {}).get("n_routed_experts")
    return {"hidden_size": hf["hidden_size"],
            # ONE routed expert's width (what moe_roofline's expert_work
            # reads); the leading dense layers' under a key of its own
            "intermediate_size": hf["moe_intermediate_size"],
            "dense_intermediate_size": hf["intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "num_dense_layers": hf["first_k_dense_replace"],
            "num_heads": hf["num_attention_heads"],
            "q_lora_rank": hf["q_lora_rank"],
            "kv_lora_rank": hf["kv_lora_rank"],
            "qk_nope_head_dim": nope, "qk_rope_head_dim": rope,
            "v_head_dim": hf["v_head_dim"],
            "softmax_scale": (nope + rope) ** -0.5,
            "rope_theta": float(hf["rope_parameters"]["rope_theta"]),
            "index_heads": hf["index_n_heads"],
            "index_head_dim": hf["index_head_dim"],
            "index_topk": hf["index_topk"],
            "index_rope_dim": rope,
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            # the ROUTER's width is the published count; the key itself
            # counts the experts held here (ids 0 onward)
            "num_experts": cut["published"] if cut else held,
            "experts_held": held, "first_expert_held": 0,
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "num_shared_experts": hf["n_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "routed_scaling_factor": hf["routed_scaling_factor"]}


def program_widths(hf):
    assert hf["rope_parameters"]["rope_type"] == "default" \
        and hf["n_group"] == hf["topk_group"] == 1 \
        and hf["moe_layer_freq"] == 1 and not hf["attention_bias"] \
        and hf["qk_head_dim"] == hf["qk_nope_head_dim"] \
        + hf["qk_rope_head_dim"], hf
    a = arch(hf)
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "moe_intermediate_size": hf["moe_intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "first_k_dense_replace": hf["first_k_dense_replace"],
            "num_heads": hf["num_attention_heads"],
            "head_dim": hf["qk_head_dim"],
            "q_lora_rank": hf["q_lora_rank"],
            "kv_lora_rank": hf["kv_lora_rank"],
            "qk_nope_head_dim": hf["qk_nope_head_dim"],
            "qk_rope_head_dim": hf["qk_rope_head_dim"],
            "v_head_dim": hf["v_head_dim"],
            "rope_theta": a["rope_theta"], "rope_scaling": None,
            "rms_norm_eps": hf["rms_norm_eps"],
            "index_topk": hf["index_topk"],
            "index_heads": hf["index_n_heads"],
            "index_head_dim": hf["index_head_dim"],
            "index_rope_dim": hf["qk_rope_head_dim"],
            "index_q_latent": True,
            "vocab_size": hf["vocab_size"],
            "num_experts": a["num_experts"],
            "experts_held": a["experts_held"],
            "first_expert_held": a["first_expert_held"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "n_shared_experts": hf["n_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "scoring_func": hf["scoring_func"],
            "topk_method": hf["topk_method"],
            "routed_scaling_factor": float(hf["routed_scaling_factor"]),
            "tie_embeddings": hf["tie_word_embeddings"]}


# ----------------------------------------------------------------- pieces
def _by_rows(f, x):
    """``f(rows)`` (a function of each row alone; an array or a tuple of
    arrays a row) over x [S, ...], ``ROW_BLOCK`` rows at a time."""
    s = x.shape[0]
    if s <= ROW_BLOCK:
        return f(x)
    n = -(-s // ROW_BLOCK)
    cut = jnp.pad(x, ((0, n * ROW_BLOCK - s),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(f, cut.reshape(n, ROW_BLOCK, *x.shape[1:]))
    return jax.tree_util.tree_map(
        lambda t: t.reshape(n * ROW_BLOCK, *t.shape[2:])[:s], out)


def rotate(a, x, positions, dims=None):
    """x [S, H, D] with its leading ``dims`` (None: all) rotated, split-half
    pairs, plain rotary at the model's theta."""
    return ref.rope({"rotary_dim": dims or x.shape[-1],
                     "rope_theta": a["rope_theta"]}, x, positions)


def swiglu(p, x):
    """A SwiGLU of matrices as stored (``ref.swiglu`` through
    :func:`matmul`)."""
    g = matmul(x, p["w_gate"])
    return matmul(g / (1.0 + jnp.exp(-g)) * matmul(x, p["w_up"]),
                  p["w_down"])


def _rel_gap(top, k):
    """Relative gap between the k-th and the (k+1)-th of sorted ``top`` (0
    where both are 0: an indexer score is exactly 0 where no head's product
    is positive, and a tie of zeros goes to the lower position in the
    program and here alike)."""
    return (top[:, k - 1] - top[:, k]) \
        / jnp.maximum(jnp.abs(top[:, k - 1]), 1e-30)


# --------------------------------------------------------------- selection
def index_keys(a, p, y):
    """The indexer's one key a token [S, Di] and its head weights [S, Hi] of
    the normed rows y."""
    pos = jnp.arange(y.shape[0])
    k_i = rotate(a, ref.layer_norm(p["ki_norm"], matmul(y, p["w_ki"]),
                                   a["norm_eps"])[:, None], pos,
                 a["index_rope_dim"])
    return k_i[:, 0], matmul(y, p["w_w"])


def selection(a, p, y, c_q):
    """``(positions [S, topk] int32, gaps [S])``: each row's ``index_topk``
    best positions of those it sees by the indexer's dense scores (a row
    that sees no more than ``topk`` gets them all among its picks), and its
    relative gap between the ``topk``-th and the next score (1.0 where it
    sees no more than ``topk``). ``Q_BLOCK`` rows at a time."""
    s, hi, di = y.shape[0], a["index_heads"], a["index_head_dim"]
    topk = a["index_topk"]
    k_i, w = index_keys(a, p, y)
    scale = (hi * di) ** -0.5
    pos = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        at = jnp.minimum(rows, s - 1)
        q_i = rotate(a, matmul(c_q[at], p["w_qi"]).reshape(Q_BLOCK, hi, di),
                     at, a["index_rope_dim"])
        each = jnp.einsum("qhd,sd->qhs", q_i, k_i)
        score = (jnp.maximum(each, 0.0) * w[at][..., None]).sum(1) * scale
        seen = rows[:, None] >= pos[None, :]
        top, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), topk + 1)
        return idx[:, :topk], jnp.where(rows >= topk, _rel_gap(top, topk),
                                        1.0)

    n_blocks = -(-s // Q_BLOCK)
    idx, gaps = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    return idx.reshape(-1, topk)[:s], gaps.reshape(-1)[:s]


# --------------------------------------------------------------- attention
def attention(a, p, y, select="indexer"):
    """Expanded latent attention over one sequence's normed rows y [S, d],
    each row over the positions the indexer selected -> (out [S, d], the
    indexer's near-tie gaps [S]). ``select``: ``"indexer"``, or ``"all"``
    for dense attention (what a test holds the short-context case
    against)."""
    s, h = y.shape[0], a["num_heads"]
    nope, rope, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
    r, eps, pos = a["kv_lora_rank"], a["norm_eps"], jnp.arange(s)
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    c_q = _by_rows(lambda t: ref.rms_norm(
        p["q_norm"], matmul(t, p["w_qa"]), eps), y)
    ckv = _by_rows(lambda t: matmul(t, p["w_kva"]), y)
    c_kv = ref.rms_norm(p["kv_norm"], ckv[:, :r], eps)
    k_r = rotate(a, ckv[:, None, r:], pos)                  # [S, 1, rope]
    sparse = select == "indexer" and s > a["index_topk"]
    picks, gaps = selection(a, p, y, c_q) if sparse \
        else (None, jnp.ones((s,)))
    w_qb = p["w_qb"].reshape(-1, h, nope + rope)
    w_kvb = p["w_kvb"].reshape(r, h, nope + dv)
    w_o = p["wo"].reshape(h, dv, -1)

    def heads(acc, first):
        """Heads ``first`` to ``first + hb``: their output through their
        rows of W_o, added to ``acc`` [S, d]."""
        cols = lambda w: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, first, hb, axis=1).reshape(w.shape[0], -1)
        q = matmul(c_q, cols(w_qb)).reshape(s, hb, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotate(a, q[..., nope:], pos)],
                            -1)
        kv = matmul(c_kv, cols(w_kvb)).reshape(s, hb, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (s, hb, rope))], -1)
        v = kv[..., nope:]

        def block(start):
            rows = start + jnp.arange(Q_BLOCK)
            at = jnp.minimum(rows, s - 1)
            seen = rows[:, None] >= pos[None, :]              # [Q, S]
            if sparse:
                chosen = jnp.zeros((Q_BLOCK, s), bool).at[
                    jnp.arange(Q_BLOCK)[:, None], picks[at]].set(True)
                seen = jnp.logical_and(seen, chosen)
            scores = jnp.einsum("qhd,khd->hqk", q[at], k) \
                * a["softmax_scale"]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            e = jnp.exp(scores - scores.max(-1, keepdims=True))
            return jnp.einsum("hqk,khd->qhd",
                              e / e.sum(-1, keepdims=True), v)

        n_blocks = -(-s // Q_BLOCK)
        out = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
        out = out.reshape(n_blocks * Q_BLOCK, hb * dv)[:s]
        rows_o = jax.lax.dynamic_slice_in_dim(w_o, first, hb, axis=0)
        return acc + matmul(out, rows_o.reshape(hb * dv, -1)), None

    out, _ = jax.lax.scan(heads, jnp.zeros_like(y), jnp.arange(0, h, hb))
    return out, gaps


# --------------------------------------------------------------------- MLP
def router(a, p, x):
    """Gates [S, E] over the router's WHOLE width (a token's weights at its
    chosen experts, 0 elsewhere) and each token's relative gap between the
    k-th and the (k+1)-th of the SELECTION scores ``s + b``: within the
    served precision's rounding the served top-k set may differ, and the
    outputs legitimately with it."""
    k = a["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router"])
    top, idx = jax.lax.top_k(scores + p["router_bias"], k + 1)
    gap = _rel_gap(top, k)
    idx = idx[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if a["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * a["routed_scaling_factor"]
    return (jax.nn.one_hot(idx, a["num_experts"]) * w[..., None]).sum(1), gap


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def experts(a, p, x, stacks=None, layer=0):
    """The held experts' part of the layer and the shared expert: x [S, d]
    float32; ``p`` the layer's ``moe`` subtree. Its expert matrices are
    ``p``'s own [held, ., .] or, ``stacks`` [L_moe * held, ., .], every
    expert layer's AS STORED (a free reshape: a layer's slice of the stack
    would be a copy of 1.2 GB), of which the held expert ``i`` of this layer
    is row ``layer * held + i``. Every held expert over every token, one at
    a time, its gate zeroing the tokens that did not choose it (the held
    expert ``i`` is the router's id ``first_expert_held + i``); a choice of
    an expert that is not here adds nothing."""
    gates, gaps = router(a, p, x)
    first = a["first_expert_held"]
    if stacks is None:
        stacks = {k: p[k] for k in EXPERT_LEAVES}
    held = p["w_up"].shape[0] if "w_up" in p else a["experts_held"]

    def one(i, acc):
        w = {k: jax.lax.dynamic_index_in_dim(stacks[k], layer * held + i,
                                             keepdims=False)
             for k in EXPERT_LEAVES}
        g = jax.lax.dynamic_index_in_dim(gates, first + i, axis=1,
                                         keepdims=False)
        return acc + g[:, None] * swiglu(w, x)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    if "shared" in p:
        out = out + swiglu(p["shared"], x)
    return out, gaps


def block(a, p, x, select="indexer", stacks=None, layer=0):
    """One layer over x [S, d] -> (x, (the router's gaps [S]; ones for a
    dense layer, the indexer's gaps [S])). ``stacks``, ``layer``:
    :func:`experts`'."""
    norm = lambda q, t: ref.rms_norm(q, t, a["norm_eps"])  # noqa: E731
    out, index_gap = attention(a, p["attn"], norm(p["attn_norm"], x), select)
    x = x + out

    def mlp(rows):
        u = norm(p["mlp_norm"], rows)
        if "moe" not in p:
            return swiglu(p["mlp"], u), jnp.ones(u.shape[0])
        return experts(a, p["moe"], u, stacks, layer)

    out, router_gap = _by_rows(mlp, x)
    return x + out, (router_gap, index_gap)


def _walk(a, params, ids):
    """-> (logits [S, V], (router gaps [L, S], indexer gaps [L, S])): the
    leading dense layers' stack, then the expert layers', each scanned, the
    small leaves cast to float32 as they are used, the matrices as stored
    (:func:`matmul`)."""
    def small_f32(tree):
        return {k: small_f32(v) if isinstance(v, dict) else
                v if k in MATRICES else v.astype(jnp.float32)
                for k, v in tree.items()}

    gaps = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids].astype(jnp.float32)
        for name in ("dense_layers", "layers"):
            stack = params.get(name)
            if stack is None:
                continue
            # the expert matrices stay outside the walk as [L_moe * held,
            # ., .] (free reshapes of the stored leaves); the rest is
            # scanned, so that ONE layer's slices are held at a time
            moe = stack.get("moe", {})
            stacks = {k: moe[k].reshape(-1, *moe[k].shape[2:])
                      for k in EXPERT_LEAVES if k in moe} or None
            rest = stack if stacks is None else {**stack, "moe": {
                k: w for k, w in moe.items() if k not in EXPERT_LEAVES}}
            n = stack["attn_norm"]["scale"].shape[0]
            x, g = jax.lax.scan(
                lambda x, inp: block(a, small_f32(inp[0]), x, stacks=stacks,
                                     layer=inp[1]), x, (rest, jnp.arange(n)))
            gaps.append(g)
        h = ref.rms_norm({"scale": params["final_norm"]["scale"].astype(
            jnp.float32)}, x, a["norm_eps"])
        s = h.shape[0]
        if 4 * s * a["vocab_size"] > DENSE_BYTES:
            h = h[-TAIL_ROWS:]
        logits = matmul(h, params["lm_head"]["kernel"])
    if logits.shape[0] != s:
        logits = TailLogits(logits, s)
    return logits, tuple(jnp.concatenate(g) for g in zip(*gaps))


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def router_gaps(a, params, ids):
    """[L_moe, S]: per expert layer and position, :func:`router`'s relative
    gap in THIS forward (float32, highest): what a parity check counts its
    near-ties from."""
    n_dense = a["num_dense_layers"] if "dense_layers" in params else 0
    return _walk(a, params, ids)[1][0][n_dense:]


def index_gaps(a, params, ids):
    """[L, S]: per layer and position, the relative gap between the row's
    ``topk``-th and (``topk`` + 1)-th indexer score where it sees more than
    ``topk`` positions (1.0 where it does not): within the served
    precision's rounding the served SELECTION may differ there, and the
    outputs legitimately with it."""
    return _walk(a, params, ids)[1][1]


# ------------------------------------------------------------------- counts
def attention_params(a):
    """Latent attention's five projections and the indexer's three."""
    d, h = a["hidden_size"], a["num_heads"]
    nope, rope, v = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                     a["v_head_dim"])
    attn = (d * a["q_lora_rank"] + a["q_lora_rank"] * h * (nope + rope)
            + d * (a["kv_lora_rank"] + rope)
            + a["kv_lora_rank"] * h * (nope + v) + h * v * d)
    index = a["q_lora_rank"] * a["index_heads"] * a["index_head_dim"] \
        + d * (a["index_head_dim"] + a["index_heads"])
    return attn, index


def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers:
    attention and the indexer, and in a dense layer its SwiGLU, in an
    expert layer the router, its OWN ``num_experts_per_tok`` experts and
    the shared ones; then the output head."""
    d = a["hidden_size"]
    dense = 3 * d * a["dense_intermediate_size"]
    moe = d * a["num_experts"] + 3 * d * a["intermediate_size"] * (
        a["num_experts_per_tok"] + a["num_shared_experts"])
    n_dense = a["num_dense_layers"]
    return (a["num_layers"] * sum(attention_params(a)) + n_dense * dense
            + (a["num_layers"] - n_dense) * moe + d * a["vocab_size"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met, expanded attention's two products over the
    SELECTED pairs ((nope + rope) + v per pair and head, 2 FLOPs each; a
    window of ``topk`` counts as many) and the indexer's scores over all
    causal pairs, three times with the backward. The training path does not
    run this model; the count is the family's contract."""
    selected = 3 * 2 * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
                        + a["v_head_dim"]) * a["num_heads"] \
        * a["num_layers"] * flops.causal_pairs(seq, a["index_topk"]) / seq
    scored = 3 * 2 * a["index_heads"] * a["index_head_dim"] \
        * a["num_layers"] * flops.causal_pairs(seq) / seq
    return 6 * matmul_params(a) + selected + scored


# what the rooflines of the selection count over a LATENT pool
# (``layer_kinds`` below): the program attends in absorbed form, so a cached
# token is ONE row read once for all heads and a pair costs every head a
# product over the row's 576 values and one over its 512-wide latent
def latent_row_bytes(a, itemsize=2):
    """A cached token's row in one layer as the pool holds it: latent and
    rotated key on whole 128-lane tiles (576 values on 640 lanes: 1,280
    B)."""
    width = a["kv_lora_rank"] + a["qk_rope_head_dim"]
    return -(-width // LANES) * LANES * itemsize


def pair_flops(a):
    """FLOPs of one (row, selected token) pair in one layer: every head's
    score over the row's ``kv_lora_rank + qk_rope_head_dim`` values and its
    value product over ``kv_lora_rank``, 2 a multiply-add."""
    return a["num_heads"] * 2 * (2 * a["kv_lora_rank"]
                                 + a["qk_rope_head_dim"])


def selected_attention_work(a, sel_pairs, ctx_tokens, itemsize=2):
    """``(FLOPs, bytes)`` the attention over atoms needs in all layers for
    ``sel_pairs`` (row, selected token) pairs and the chunks'
    ``ctx_tokens`` of context, each read once as a latent row."""
    return (a["num_layers"] * sel_pairs * pair_flops(a),
            a["num_layers"] * ctx_tokens * latent_row_bytes(a, itemsize))


def selected_rows_work(a, dec_sel_tokens, itemsize=2):
    """``(FLOPs, bytes)`` of the one-token rows' attention in all layers:
    every SELECTED token's latent row read once for all heads, and a pair's
    products of every head over it."""
    return (a["num_layers"] * dec_sel_tokens * pair_flops(a),
            a["num_layers"] * dec_sel_tokens * latent_row_bytes(a, itemsize))


def index_work(a, attn_pairs, dec_ctx_tokens, itemsize=2):
    """``(FLOPs, bytes)`` of the indexer's scores in all layers: the
    chunks' (row, cached token) pairs at ``heads x dim x 2`` each, and the
    one-token rows' whole contexts read as indexer keys."""
    return (a["num_layers"] * attn_pairs
            * a["index_heads"] * a["index_head_dim"] * 2,
            a["num_layers"] * dec_ctx_tokens * a["index_head_dim"] * itemsize)


# ------------------------------ the kind of layer it has: a selection
# (``benchmark.reference.layer_kind``), under the labels and the kernels'
# names ``inference/v2/dsa.py`` gives either kind of pool
SCOPES = ("dsa_index", "dsa_select", "dsa_attend")
KERNELS = (("dsa_index_scores", "dsa_index"), ("dsa_select", "dsa_select"),
           ("dsa_prefill", "dsa_attend"))


def layer_kinds():
    """Every layer's attention reads latent rows a learned indexer SELECTED.
    ``dsa_index``: the indexer's projections, norm, rotary, pool write, the
    gather of a sequence's keys through its block table and the scores;
    ``dsa_select``: the selection; ``dsa_attend``: whatever gathers, masks
    and attends over the selected rows (``dsa_rows``, the one-token rows'
    part, inside it: the gather is XLA's, no kernel of its own)."""
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
    ``round`` record x 256 B a token and layer) over the HBM bandwidth, PLUS
    the prompt chunks' scores' FLOPs (``attn_pairs`` x 32 heads x 128 x 2 a
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
    of the ``round`` record) every head's two products over the latent row
    (``pair_flops``: 64 x 2 x (576 + 512)), in each layer; and the chunk's
    context read once as latent rows (the record has no per-chunk contexts,
    so their floor: a chunk of n <= ``max_tokens_per_batch`` rows that
    covers P pairs of ``attn_pairs`` reads at least P / n rows):
    ``selected_attention_work`` through ``flops.roofline_seconds``; against
    the time under ``dsa_attend`` that is not the one-token rows'
    (``dsa_rows``)."""
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
    ``ragged_forward``): the latent row of every SELECTED token
    (``dec_sel_tokens`` of the ``round`` record; 1,280 B a token and layer)
    read once for all 64 heads, and their products over it: the LARGER of
    the bytes' and the FLOPs' seconds (``flops.roofline_seconds``: one
    gathered row serves 64 heads, 109 FLOP a byte, so the route is no pure
    read as a K-and-V pool's is); against the time under ``dsa_rows``: the
    gather of the selected rows through the block table and the attention
    over them."""
    a, peaks = arch(obs["config"]), obs["peaks"]

    def work(d, _counted, seconds):
        tokens, took = d.get("dec_sel_tokens"), seconds.get("dsa_rows")
        if not tokens or not took:
            return None
        return flops.roofline_seconds(*selected_rows_work(a, tokens),
                                      peaks)[0], took
    return work
