"""``model_type: olmoe`` — pre-norm sequential block, RMSNorm, multi-head
attention with full rotary embedding and an RMSNorm with a learned scale over
the WHOLE q projection and the whole k projection (before the split into
heads, before rotary), and a sparse-expert MLP: one bias-free router to
``num_experts`` logits, softmax in float32 over all of them, the top
``num_experts_per_tok`` kept and (``norm_topk_prob: false``) NOT renormalised,
each expert a SwiGLU, no shared expert (Muennighoff et al. 2024,
arXiv:2409.02060, and HF ``modeling_olmoe``; ``clip_qkv`` is null).

Plain, and independent of ``parallel/moe.py``: gates are a one-hot of the
top-k times its weights, and the experts are walked one at a time, every
expert over every token with the gates zeroing the rest, so one expert's
``[S, intermediate]`` is all that is held beside the layer's weights."""
import math

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import reference as ref


def arch(hf):
    heads = hf["num_attention_heads"]
    head_dim = hf["hidden_size"] // heads
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "num_heads": heads,
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": head_dim, "rotary_dim": head_dim,
            "vocab_size": hf["vocab_size"],
            "rope_theta": hf["rope_theta"],
            "sliding_window": None,
            "norm_eps": hf["rms_norm_eps"],
            "num_experts": hf["num_experts"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "norm_topk_prob": hf["norm_topk_prob"]}


def program_widths(hf):
    a = arch(hf)
    return {**{k: a[k] for k in (
        "hidden_size", "intermediate_size", "num_layers", "num_heads",
        "num_kv_heads", "head_dim", "vocab_size", "sliding_window",
        "num_experts", "num_experts_per_tok", "norm_topk_prob")},
        "qk_norm": True}


def attention(a, p, x):
    """Causal softmax attention over one sequence x [S, d], with the norm
    over the projections (``reference.attention`` has no place for it)."""
    s = x.shape[0]
    h, hk, d = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    q = ref.rms_norm(p["q_norm"], x @ p["wq"], a["norm_eps"])   # [S, h*d]
    k = ref.rms_norm(p["k_norm"], x @ p["wk"], a["norm_eps"])   # [S, hk*d]
    v = (x @ p["wv"]).reshape(s, hk, d)
    pos = jnp.arange(s)
    q = ref.rope(a, q.reshape(s, h, d), pos)
    k = ref.rope(a, k.reshape(s, hk, d), pos)
    k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    w = jnp.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return jnp.einsum("hqk,khd->qhd", w, v).reshape(s, h * d) @ p["wo"]


def router(a, p, x):
    """Gates [S, E] (a token's top-k weights at its experts, 0 elsewhere) and
    each token's relative gap between its k-th and (k+1)-th probability:
    where that is within the served precision's rounding, the served top-k
    SET may differ and the outputs legitimately with it."""
    k = a["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k + 1)
    gap = (top_w[:, k - 1] - top_w[:, k]) / top_w[:, k - 1]
    top_w, top_i = top_w[:, :k], top_i[:, :k]
    if a["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(top_i, a["num_experts"]) * top_w[..., None]).sum(1)
    return gates, gap


def experts(a, p, x):
    gates, gap = router(a, p, x)

    def one(acc, e):            # e: one expert's three matrices and its gate
        w_gate, w_up, w_down, g = e
        y = ref.swiglu({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, x)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return out, gap


def block(a, p, x):
    """-> (x, the router's top-k gaps [S] of this layer)."""
    norm = lambda q, y: ref.rms_norm(q, y, a["norm_eps"])  # noqa: E731
    x = x + attention(a, p["attn"], norm(p["attn_norm"], x))
    y, gap = experts(a, p["moe"], norm(p["mlp_norm"], x))
    return x + y, gap


def sequence_logits(a, params, ids):
    return ref.decoder_logits(
        params, ids, lambda p, x: block(a, p, x)[0],
        lambda p, x: ref.rms_norm(p, x, a["norm_eps"]))


def router_gaps(a, params, ids):
    """[L, S]: per layer and position, :func:`router`'s relative gap between
    the k-th and (k+1)-th probability in THIS forward (float32, highest):
    what a parity check counts its near-ties from."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"][ids])
        _, gaps = jax.lax.scan(lambda x, p: block(a, f32(p), x), x,
                               params["layers"])
    return gaps


def matmul_params(a):
    """Weights a token meets in a matrix product: attention, its OWN
    ``num_experts_per_tok`` experts, the router and the output head."""
    d = a["hidden_size"]
    mlp = a["num_experts_per_tok"] * 3 * d * a["intermediate_size"] \
        + d * a["num_experts"]
    return a["num_layers"] * (flops.attention_params(a) + mlp) \
        + d * a["vocab_size"]


def train_flops_per_token(a, seq):
    return 6 * matmul_params(a) + flops.attention_train_flops(a, seq)
