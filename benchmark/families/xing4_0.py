"""``model_type: xing4_0`` (XingChen-AGI/Xing4.0-29B-A4B) — the equations of
ISSUE 30, in plain ``jax.numpy``; every point its ``config.json`` does not
pin is marked (A) here and listed under ``assumed`` in the configuration's
file.

*Residual streams* (mHC, manifold-constrained hyper-connections, DeepSeek-AI,
arXiv:2512.24880, over hyper-connections, arXiv:2409.19606; ``n = hc_mult``).
A token's state is ``X`` [n, d]; ``X_0`` is the embedding repeated n times
(A). Each sublayer F (attention, then the MLP, each with its own maps):
``x' = RMSNorm_w(vec(X))`` over all n*d values; ``H~ = a * (x' Phi) + b`` for
the three maps pre [n], post [n], res [n, n] (``Phi`` one [n*d, n + n + n*n]
matrix, columns pre | post | res; ``a`` three scalars); ``H_pre =
sigmoid(H~_pre)``, ``H_post = 2 sigmoid(H~_post)``, ``H_res = SK(exp(clip(
H~_res)))`` with SK = ``hc_sinkhorn_iters`` rounds of "each column over its
sum + hc_eps, then each row" (A: column first, clamp before the exp);
``u = H_pre X``; ``y = F(RMSNorm(u))`` (A: the block's usual pre-norm);
``X <- H_res X + H_post^T y``. After the last layer ``h = sum_i X_i`` (A).

*Latent attention* (DeepSeek-V2's MLA), in EXPANDED form and without a
cache: ``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` -> heads of (nope | rope);
``[c_kv | k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv)``; YaRN rotary on q's rope
part and on the ONE k_r all heads share; ``[k_nope | v]_h = c_kv W_kvb``;
``score = (q_nope . k_nope + q_r . k_r) * (nope + rope)^-1/2 * m^2``,
``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax; out =
``[o_1 .. o_H] W_o``. Rotary pairs are split-half, as the program's weight
layout has them (A: a column permutation of the published interleaved pairs).

*MLP.* Layers < ``first_k_dense_replace`` (the tree's ``dense_layers``): a
SwiGLU of ``intermediate_size``. The rest: ``s = sigmoid(u W_r)`` over the
experts; the top ``num_experts_per_tok`` of ``s + b`` (the ``noaux_tc`` bias,
for the CHOICE only); weights ``s[chosen] / (sum + 1e-20) *
routed_scaling_factor``; plus the shared expert's SwiGLU of every token.

Plain, and independent of ``parallel/moe.py``, ``ops/`` and ``inference/``:
experts are walked one at a time and cast to float32 one at a time, so that
one expert's matrices are all that is held in float32 beside the bf16 tree;
attention runs in blocks of queries; the Sinkhorn loop is written out. The
multi-token-prediction block (``num_nextn_predict_layers``) is no part of the
trunk's next-token logits and is not here."""
import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

Q_BLOCK = 512       # queries a block of the reference's attention takes


def arch(hf):
    rs = hf["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
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
            "softmax_scale": (nope + rope) ** -0.5 * m * m,
            "rope_theta": hf["rope_theta"], "rope_scaling": rs,
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            "num_experts": hf["n_routed_experts"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "num_shared_experts": hf["n_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "routed_scaling_factor": hf["routed_scaling_factor"],
            "hc_mult": hf["hc_mult"],
            "hc_sinkhorn_iters": hf["hc_sinkhorn_iters"],
            "hc_eps": hf["hc_eps"],
            "hc_clamp": (hf["mhc_h_res_clamp_min"],
                         hf["mhc_h_res_clamp_max"])}


def program_widths(hf):
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "moe_intermediate_size": hf["moe_intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "first_k_dense_replace": hf["first_k_dense_replace"],
            "num_heads": hf["num_attention_heads"],
            "q_lora_rank": hf["q_lora_rank"],
            "kv_lora_rank": hf["kv_lora_rank"],
            "qk_nope_head_dim": hf["qk_nope_head_dim"],
            "qk_rope_head_dim": hf["qk_rope_head_dim"],
            "v_head_dim": hf["v_head_dim"],
            "vocab_size": hf["vocab_size"],
            "num_experts": hf["n_routed_experts"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "n_shared_experts": hf["n_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "scoring_func": hf["scoring_func"],
            "topk_method": hf["topk_method"],
            "routed_scaling_factor": float(hf["routed_scaling_factor"]),
            "hc_mult": hf["hc_mult"],
            "hc_sinkhorn_iters": hf["hc_sinkhorn_iters"],
            "rope_scaling": hf["rope_scaling"]}


# ------------------------------------------------------------------ rotary
def yarn_frequencies(a):
    """[rope / 2]: each rotary frequency blended with itself / ``factor`` by
    the linear ramp between the two correction dimensions (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V2 computes it); the cos/sin factor
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` is 1."""
    rs, dim, base = a["rope_scaling"], a["qk_rope_head_dim"], a["rope_theta"]
    freq = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / rs["factor"] * ramp + freq * (1.0 - ramp)


def rotate(a, x, positions):
    """x [S, ..., rope], split-half pairs."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_frequencies(a)
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), ang.shape[1])
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


# --------------------------------------------------------------- attention
def attention(a, p, x):
    """Expanded latent attention over one sequence x [S, d]; the [Q_BLOCK,
    S] scores of one block of queries and head group are all that is held."""
    s, h = x.shape[0], a["num_heads"]
    nope, r = a["qk_nope_head_dim"], a["kv_lora_rank"]
    eps, pos = a["norm_eps"], jnp.arange(s)
    q = (ref.rms_norm(p["q_norm"], x @ p["w_qa"], eps) @ p["w_qb"]) \
        .reshape(s, h, -1)
    ckv = x @ p["w_kva"]
    kv = (ref.rms_norm(p["kv_norm"], ckv[:, :r], eps) @ p["w_kvb"]) \
        .reshape(s, h, -1)
    k_r = rotate(a, ckv[:, r:], pos)                        # [S, rope]
    q = jnp.concatenate([q[..., :nope], rotate(a, q[..., nope:], pos)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (s, h, k_r.shape[1]))],
        -1)
    v = kv[..., nope:]

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        qb = q[jnp.minimum(rows, s - 1)]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * a["softmax_scale"]
        scores = jnp.where((rows[:, None] >= pos[None, :])[None], scores,
                           -jnp.inf)
        w = jnp.exp(scores - scores.max(-1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v)

    n_blocks = -(-s // Q_BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    return out.reshape(n_blocks * Q_BLOCK, -1)[:s] @ p["wo"]


# --------------------------------------------------------------------- MLP
def router(a, p, x):
    """Gates [S, E] (a token's weights at its chosen experts, 0 elsewhere)
    and each token's relative gap between the k-th and the (k+1)-th of the
    SELECTION scores ``s + b``: within the served precision's rounding the
    served top-k set may differ, and the outputs legitimately with it."""
    k = a["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router"])
    top, idx = jax.lax.top_k(scores + p["router_bias"], k + 1)
    gap = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    idx = idx[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if a["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * a["routed_scaling_factor"]
    return (jax.nn.one_hot(idx, a["num_experts"]) * w[..., None]).sum(1), gap


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def experts(a, p, x, stacks, layer):
    """x [S, d] float32; ``p`` the layer's ``moe`` subtree without its
    expert matrices; those are ``stacks`` [L_moe * E, ., .] AS STORED, and
    expert ``e`` of this layer is row ``layer * E + e``: one expert at a
    time is cut out and cast to float32, nothing more is ever held."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    gates, gap = router(a, f32({"router": p["router"],
                                "router_bias": p["router_bias"]}), x)
    n = a["num_experts"]

    def one(e, acc):
        w = {k: jax.lax.dynamic_index_in_dim(stacks[k], layer * n + e,
                                             keepdims=False)
             for k in EXPERT_LEAVES}
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=1, keepdims=False)
        return acc + g[:, None] * ref.swiglu(f32(w), x)

    out = jax.lax.fori_loop(0, n, one, jnp.zeros_like(x))
    return out + ref.swiglu(f32(p["shared"]), x), gap


# ----------------------------------------------------------------- streams
def hc_maps(a, hc, X):
    """X [S, n, d] -> H_pre [S, n], H_post [S, n], H_res [S, n, n]."""
    s, n, d = X.shape
    x = ref.rms_norm(hc["norm"], X.reshape(s, n * d), a["norm_eps"])
    maps = x @ hc["phi"]                                     # [S, 2n + n*n]
    b = hc["b"]
    h_pre = jax.nn.sigmoid(hc["a"][0] * maps[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(hc["a"][1] * maps[:, n:2 * n] + b[n:2 * n])
    h_res = jnp.exp(jnp.clip(hc["a"][2] * maps[:, 2 * n:] + b[2 * n:],
                             *a["hc_clamp"])).reshape(s, n, n)
    for _ in range(a["hc_sinkhorn_iters"]):
        h_res = h_res / (h_res.sum(1, keepdims=True) + a["hc_eps"])  # columns
        h_res = h_res / (h_res.sum(2, keepdims=True) + a["hc_eps"])  # rows
    return h_pre, h_post, h_res


def sublayer(a, hc, X, f):
    """``f(u) -> (y, aux)``; -> (the streams after the sublayer, aux)."""
    h_pre, h_post, h_res = hc_maps(a, hc, X)
    y, aux = f(jnp.einsum("sn,snd->sd", h_pre, X))
    return jnp.einsum("sij,sjd->sid", h_res, X) + h_post[:, :, None] \
        * y[:, None, :], aux


def block(a, p, X, stacks=None, layer=None):
    """One layer over the streams X [S, n, d]; ``p`` AS STORED (an expert
    layer's without its expert matrices: :func:`experts`), cast to float32
    here. -> (X, the router's top-k gaps [S]; ones for a dense layer)."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    norm = lambda q, y: ref.rms_norm(f32(q), y, a["norm_eps"])  # noqa: E731
    X, _ = sublayer(a, f32(p["hc_attn"]), X, lambda u: (attention(
        a, f32(p["attn"]), norm(p["attn_norm"], u)), None))

    def mlp(u):
        u = norm(p["mlp_norm"], u)
        if "moe" not in p:
            return ref.swiglu(f32(p["mlp"]), u), jnp.ones(u.shape[0])
        return experts(a, p["moe"], u, stacks, layer)

    return sublayer(a, f32(p["hc_mlp"]), X, mlp)


def _walk(a, params, ids):
    """-> (logits [S, V], gaps [L, S]); the leading dense layers' stack is
    scanned, then the expert layers', whose expert matrices stay outside the
    scan as [L_moe * E, ., .] (free reshapes of the stored leaves)."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"][ids])
        X = jnp.repeat(x[:, None, :], a["hc_mult"], axis=1)
        gaps = []
        if "dense_layers" in params:
            X, g = jax.lax.scan(lambda X, p: block(a, p, X), X,
                                params["dense_layers"])
            gaps.append(g)
        layers = params["layers"]
        stacks = {k: layers["moe"][k].reshape(-1, *layers["moe"][k].shape[2:])
                  for k in EXPERT_LEAVES}
        rest = {**layers, "moe": {k: w for k, w in layers["moe"].items()
                                  if k not in EXPERT_LEAVES}}
        n_moe = layers["mlp_norm"]["scale"].shape[0]
        X, g = jax.lax.scan(
            lambda X, inp: block(a, inp[0], X, stacks, inp[1]), X,
            (rest, jnp.arange(n_moe)))
        gaps.append(g)
        h = ref.rms_norm({"scale": f32(params["final_norm"]["scale"])},
                         X.sum(1), a["norm_eps"])
        # one plain product: the TPU compiler fuses the head's cast into it
        # (no float32 copy of the 131072 x 3584 head) and sinks a caller's
        # row slice through it (benchmark.parity keeps the last rows only)
        logits = h @ f32(params["lm_head"]["kernel"])
    return logits, jnp.concatenate(gaps)


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def router_gaps(a, params, ids):
    """[L_moe, S]: per expert layer and position, :func:`router`'s relative
    gap in THIS forward (float32, highest): what a parity check counts its
    near-ties from."""
    n_dense = a["num_dense_layers"] if "dense_layers" in params else 0
    return _walk(a, params, ids)[1][n_dense:]


# ------------------------------------------------------------------- FLOPs
def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers:
    latent attention's five projections, the hyper-connection maps of both
    sublayers, and in a dense layer its SwiGLU, in an expert layer the
    router, its OWN ``num_experts_per_tok`` experts and the shared ones;
    then the output head."""
    d, h = a["hidden_size"], a["num_heads"]
    nope, rope, v = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                     a["v_head_dim"])
    n = a["hc_mult"]
    attn = (d * a["q_lora_rank"] + a["q_lora_rank"] * h * (nope + rope)
            + d * (a["kv_lora_rank"] + rope)
            + a["kv_lora_rank"] * h * (nope + v) + h * v * d)
    hc = 2 * n * d * (2 * n + n * n)
    dense = 3 * d * a["dense_intermediate_size"]
    moe = d * a["num_experts"] + 3 * d * a["intermediate_size"] * (
        a["num_experts_per_tok"] + a["num_shared_experts"])
    n_dense = a["num_dense_layers"]
    return (a["num_layers"] * (attn + hc) + n_dense * dense
            + (a["num_layers"] - n_dense) * moe + d * a["vocab_size"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met plus expanded attention's two products over
    the causal pairs ((nope + rope) + v per pair and head, 2 FLOPs each,
    three times with the backward). The training path does not run this
    model yet; the count is the family's contract."""
    pairs = seq * (seq + 1) // 2
    attn = 3 * 2 * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
                    + a["v_head_dim"]) * a["num_heads"] * a["num_layers"] \
        * pairs / seq
    return 6 * matmul_params(a) + attn
