"""``model_type: deepseek_v2`` (deepseek-ai/DeepSeek-V2, arXiv:2405.04434) —
the equations of ISSUE 33 in plain ``jax.numpy``, for ONE CHIP'S SHARE of an
expert-parallel deployment: the router is as wide as published, the chip
holds some of its experts, and what the others would have added is left out
(``benchmark/README.md``, "What ``reduced`` may hold"). Points the published
``config.json`` does not pin are marked (A) and listed under ``assumed`` in
the configuration's file.

*Latent attention* (MLA), in EXPANDED form and without a cache: ``c_q =
RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads of (nope | rope); ``[c_kv | k_r]
= x W_kva``, ``c_kv = RMSNorm(c_kv)``; YaRN rotary on q's rope part and on
the ONE k_r all heads share; ``[k_nope | v]_h = c_kv W_kvb``; ``score =
(q_nope . k_nope + q_r . k_r) * (nope + rope)^-1/2 * m^2``, ``m = 0.1 *
mscale_all_dim * ln(factor) + 1``; causal softmax; out = ``[o_1 .. o_H]
W_o``. Rotary pairs are split-half, as the program's weight layout has them
(A: a column permutation of the published interleaved pairs).

*Router* (expert layers): ``s = softmax(x W_g)`` over ALL ``num_experts``;
the experts lie in ``n_group`` equal runs of ids; a group's score is its
best expert's; the ``topk_group`` best groups are kept and every other
group's scores set to 0; the ``num_experts_per_tok`` largest of what is left
are chosen (ties to the lower id, as ``top_k`` breaks them); weights = those
scores, divided by their sum only if ``norm_topk_prob``, times
``routed_scaling_factor``. No selection bias.

*MLP.* Layers < ``first_k_dense_replace`` (the tree's ``dense_layers``): a
SwiGLU of ``intermediate_size``. The rest: ``sum_i w_i E_i(x)`` over the
chosen experts THAT ARE HELD (ids ``first_expert_held`` onward, as many as
the tree's expert leaves have) plus ONE SwiGLU of ``n_shared_experts x
moe_intermediate_size`` (the shared experts, which every chip computes
alike). Pre-norm residual blocks, final RMSNorm, the head over the rows the
tree has.

Plain, and independent of ``parallel/moe.py``, ``ops/`` and ``inference/``:
experts are walked and cast to float32 one at a time (a whole expert layer
in float32 is 4.6 GB), attention runs in blocks of queries."""
import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

Q_BLOCK = 128       # queries a block of the reference's attention takes


def arch(hf):
    rs = hf["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
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
            "softmax_scale": (nope + rope) ** -0.5 * m * m,
            "rope_theta": hf["rope_theta"], "rope_scaling": rs,
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            # the ROUTER's width is the published count; the key itself
            # counts the experts held here (the first of them is the
            # deployment's to say: this chip's are ids 0 onward)
            "num_experts": cut["published"] if cut else held,
            "experts_held": held, "first_expert_held": 0,
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "n_group": hf["n_group"], "topk_group": hf["topk_group"],
            "num_shared_experts": hf["n_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "routed_scaling_factor": hf["routed_scaling_factor"]}


def program_widths(hf):
    a = arch(hf)
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
            "num_experts": a["num_experts"],
            "experts_held": a["experts_held"],
            "first_expert_held": a["first_expert_held"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "n_group": hf["n_group"], "topk_group": hf["topk_group"],
            "n_shared_experts": hf["n_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "scoring_func": hf["scoring_func"],
            "topk_method": hf["topk_method"],
            "routed_scaling_factor": float(hf["routed_scaling_factor"]),
            "rope_scaling": hf["rope_scaling"]}


# ------------------------------------------------------------------ rotary
def yarn_frequencies(a):
    """[rope / 2]: each rotary frequency blended with itself / ``factor`` by
    the linear ramp between the two correction dimensions (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V2 computes it); the cos/sin factor
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` is 1 where
    the two are equal, as published (0.707 both)."""
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
    """Expanded latent attention over one sequence x [S, d]; the [H,
    Q_BLOCK, S] scores of one block of queries are all that is held."""
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
def _rel_gap(top, k):
    """Relative gap between the k-th and the (k+1)-th of sorted ``top``."""
    return (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])


def router(a, w_g, x):
    """Gates [S, E] over the router's WHOLE width (a token's weights at its
    chosen experts, 0 elsewhere) and the token's two relative near-tie gaps
    [2, S]: between the k-th and the (k+1)-th expert score among the groups
    kept, and between the ``topk_group``-th and the next GROUP score (a
    group tie swaps up to ``k`` experts at once). Within the served
    precision's rounding the served choice may differ there, and the
    outputs legitimately with it."""
    k, n_group, keep = (a["num_experts_per_tok"], a["n_group"],
                        a["topk_group"])
    s, e = x.shape[0], a["num_experts"]
    scores = jax.nn.softmax(x @ w_g, axis=-1)
    best = scores.reshape(s, n_group, e // n_group).max(-1)       # [S, G]
    top_g, groups = jax.lax.top_k(best, min(keep + 1, n_group))
    kept = jax.nn.one_hot(groups[:, :keep], n_group).sum(1) > 0   # [S, G]
    left = jnp.where(jnp.repeat(kept, e // n_group, axis=1), scores, 0.0)
    top, idx = jax.lax.top_k(left, k + 1)
    gaps = jnp.stack([_rel_gap(top, k),
                      _rel_gap(top_g, keep) if keep < n_group
                      else jnp.ones((s,))])
    w = top[:, :k]
    if a["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * a["routed_scaling_factor"]
    return (jax.nn.one_hot(idx[:, :k], e) * w[..., None]).sum(1), gaps


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def experts(a, p, x, stacks, layer):
    """x [S, d] float32; ``p`` the layer's ``moe`` subtree without its
    expert matrices; those are ``stacks`` [L_moe * held, ., .] AS STORED,
    and the held expert ``i`` of this layer (the router's id
    ``first_expert_held + i``) is row ``layer * held + i``: one expert at a
    time is cut out and cast to float32, nothing more is ever held. The
    gates of the experts that are not here are not used."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    gates, gaps = router(a, p["router"].astype(jnp.float32), x)
    held, first = a["experts_held"], a["first_expert_held"]

    def one(i, acc):
        w = {k: jax.lax.dynamic_index_in_dim(stacks[k], layer * held + i,
                                             keepdims=False)
             for k in EXPERT_LEAVES}
        g = jax.lax.dynamic_index_in_dim(gates, first + i, axis=1,
                                         keepdims=False)
        return acc + g[:, None] * ref.swiglu(f32(w), x)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    if "shared" in p:
        out = out + ref.swiglu(f32(p["shared"]), x)
    return out, gaps


def block(a, p, x, stacks=None, layer=None):
    """One pre-norm layer over x [S, d]; ``p`` AS STORED (an expert
    layer's without its expert matrices: :func:`experts`), cast to float32
    here. -> (x, the router's two gaps [2, S]; ones for a dense layer)."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    norm = lambda q, y: ref.rms_norm(f32(q), y, a["norm_eps"])  # noqa: E731
    x = x + attention(a, f32(p["attn"]), norm(p["attn_norm"], x))
    u = norm(p["mlp_norm"], x)
    if "moe" not in p:
        return x + ref.swiglu(f32(p["mlp"]), u), jnp.ones((2, u.shape[0]))
    y, gaps = experts(a, p["moe"], u, stacks, layer)
    return x + y, gaps


def _walk(a, params, ids):
    """-> (logits [S, V], gaps [L, 2, S]); the leading dense layers' stack
    is scanned, then the expert layers', whose expert matrices stay outside
    the scan as [L_moe * held, ., .] (free reshapes of the stored leaves)."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"][ids])
        gaps = []
        if "dense_layers" in params:
            x, g = jax.lax.scan(lambda x, p: block(a, p, x), x,
                                params["dense_layers"])
            gaps.append(g)
        layers = params["layers"]
        stacks = {k: layers["moe"][k].reshape(-1, *layers["moe"][k].shape[2:])
                  for k in EXPERT_LEAVES}
        rest = {**layers, "moe": {k: w for k, w in layers["moe"].items()
                                  if k not in EXPERT_LEAVES}}
        n_moe = layers["mlp_norm"]["scale"].shape[0]
        x, g = jax.lax.scan(
            lambda x, inp: block(a, inp[0], x, stacks, inp[1]), x,
            (rest, jnp.arange(n_moe)))
        gaps.append(g)
        h = ref.rms_norm({"scale": f32(params["final_norm"]["scale"])}, x,
                         a["norm_eps"])
        # one plain product: the TPU compiler fuses the head's cast into it
        # and sinks a caller's row slice through it (benchmark.parity keeps
        # the last rows only)
        logits = h @ f32(params["lm_head"]["kernel"])
    return logits, jnp.concatenate(gaps)


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def router_gap_kinds(a, params, ids):
    """[L_moe, 2, S]: per expert layer and position :func:`router`'s two
    relative gaps in THIS forward (float32, highest): expert (the k-th
    against the (k+1)-th score) and group (the ``topk_group``-th against the
    next group's best)."""
    n_dense = a["num_dense_layers"] if "dense_layers" in params else 0
    return _walk(a, params, ids)[1][n_dense:]


def router_gaps(a, params, ids):
    """[L_moe, S]: the SMALLER of the two gaps, so that a parity check
    which counts positions under the served precision's rounding counts
    both kinds of near-tie."""
    return router_gap_kinds(a, params, ids).min(1)


# ------------------------------------------------------------------- FLOPs
def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers:
    latent attention's five projections, and in a dense layer its SwiGLU, in
    an expert layer the router, its OWN ``num_experts_per_tok`` experts and
    the shared ones; then the output head."""
    d, h = a["hidden_size"], a["num_heads"]
    nope, rope, v = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                     a["v_head_dim"])
    attn = (d * a["q_lora_rank"] + a["q_lora_rank"] * h * (nope + rope)
            + d * (a["kv_lora_rank"] + rope)
            + a["kv_lora_rank"] * h * (nope + v) + h * v * d)
    dense = 3 * d * a["dense_intermediate_size"]
    moe = d * a["num_experts"] + 3 * d * a["intermediate_size"] * (
        a["num_experts_per_tok"] + a["num_shared_experts"])
    n_dense = a["num_dense_layers"]
    return (a["num_layers"] * attn + n_dense * dense
            + (a["num_layers"] - n_dense) * moe + d * a["vocab_size"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met plus expanded attention's two products over
    the causal pairs ((nope + rope) + v per pair and head, 2 FLOPs each,
    three times with the backward), for the WHOLE expert layer (a token's
    ``num_experts_per_tok`` experts wherever they lie). The training path
    does not run this model; the count is the family's contract."""
    pairs = seq * (seq + 1) // 2
    attn = 3 * 2 * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
                    + a["v_head_dim"]) * a["num_heads"] * a["num_layers"] \
        * pairs / seq
    return 6 * matmul_params(a) + attn
