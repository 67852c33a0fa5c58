"""``model_type: ouro`` — a looped language model (Ouro, arXiv:2510.25741,
and the ``modeling_ouro.py`` published beside the config): ONE stack of L
layers run U = ``total_ut_steps`` times a token over the same weights.

L layers, U passes, hidden d, H heads of D (as many key-value heads), SwiGLU
of width f, RMSNorm at ``rms_norm_eps``, full rotary embedding at
``rope_theta``, no attention or MLP bias. ``x`` starts as the embedding row.

For pass u = 0 .. U-1, for layer l = 0 .. L-1 (the weights of layer ``l``
are the SAME in every pass):

    a = RMSNorm(x; g1_l)
    q, k, v = a Wq_l, a Wk_l, a Wv_l      rotary on q and k at the token's
                                           position, the same in every pass
    k, v -> the cache's row (u x L + l)   a pass attends to ITS OWN keys and
                                           values, never another pass's
    o = softmax(q k^T / sqrt(D), causal) v
    x = x + RMSNorm(o Wo_l; g2_l)         the sublayer's OUTPUT is normed
    m = RMSNorm(x; g3_l)
    x = x + RMSNorm((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)

after layer L-1 of pass u:

    h_u = RMSNorm(x; g_final);  x = h_u   the next pass starts from h_u
    lam_u = sigmoid(h_u w_gate + b_gate)  one number a token and pass

The exit distribution: ``p_u = lam_u prod_{j<u} (1 - lam_j)`` for u < U-1,
and ``p_{U-1}`` takes what is left. A token's exit pass is the first u whose
running sum of p reaches ``early_exit_threshold``, else U-1; ``logits =
h_exit W_head``. Every pass is COMPUTED whatever the threshold (the
published code selects a state, it skips no work); at the published 1.0 the
exit pass is U-1 for every token.

Here the whole sequence is one causal attention a pass, so "its own cache
row" is simply that pass u's attention sees pass u's keys: nothing is
cached. The program's parameter names: ``attn_norm`` g1, ``attn_post_norm``
g2, ``mlp_norm`` g3, ``mlp_post_norm`` g4, ``final_norm``, ``exit_gate``.
"""
from benchmark import flops
from benchmark import reference as ref


def arch(hf):
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "num_heads": heads,
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": head_dim, "rotary_dim": head_dim,
            "vocab_size": hf["vocab_size"],
            "rope_theta": float(hf["rope_theta"]),
            "sliding_window": hf.get("sliding_window"),
            "norm_eps": hf["rms_norm_eps"],
            "passes": hf["total_ut_steps"],
            "exit_threshold": float(hf["early_exit_threshold"])}


def program_widths(hf):
    a = arch(hf)
    return {**{k: a[k] for k in (
        "hidden_size", "intermediate_size", "num_layers", "num_heads",
        "num_kv_heads", "head_dim", "vocab_size", "sliding_window")},
        "total_ut_steps": a["passes"],
        "early_exit_threshold": a["exit_threshold"],
        "sandwich_norm": True, "num_kv_layers": kv_rows(a)}


def kv_rows(a):
    """Rows of the cache a token has: one for every (pass, layer) pair."""
    return a["passes"] * a["num_layers"]


def exit_distribution(lam):
    """lam [U - 1, S], the gates of every pass but the last (whose own is
    never asked: it takes what is left) -> p [U, S]: ``lam_u prod_{j<u} (1 -
    lam_j)``, so every column sums to 1."""
    import jax.numpy as jnp

    left, p = jnp.ones(lam.shape[1:], jnp.float32), []
    for gate in lam:
        p.append(gate * left)
        left = left * (1.0 - gate)
    return jnp.stack(p + [left])


def exit_pass(lam, threshold):
    """The pass each token's logits come from [S]: the first whose running
    sum of the exit distribution reaches ``threshold``, else the last."""
    import jax.numpy as jnp

    p = exit_distribution(lam)
    last = p.shape[0] - 1
    chosen = jnp.full(p.shape[1:], last, jnp.int32)
    for u in reversed(range(last)):    # downwards: the FIRST to reach it wins
        chosen = jnp.where(p[:u + 1].sum(0) >= threshold, u, chosen)
    return chosen


def pass_states(a, params, ids):
    """``(h [U, S, d], lam [U - 1, S])``: each pass's normed output and the
    gate of every pass but the last, float32, the loop written out."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    norm = lambda p, x: ref.rms_norm(p, x, a["norm_eps"])  # noqa: E731

    def block(x, p):
        p = f32(p)
        o = ref.attention(a, p["attn"], norm(p["attn_norm"], x))
        x = x + norm(p["attn_post_norm"], o)
        m = ref.swiglu(p["mlp"], norm(p["mlp_norm"], x))
        return x + norm(p["mlp_post_norm"], m), None

    with jax.default_matmul_precision("highest"):
        g_final = f32(params["final_norm"])
        x = f32(params["embed"]["embedding"][ids])
        hs, lams = [], [jnp.zeros((0, len(ids)), jnp.float32)]
        for u in range(a["passes"]):
            x, _ = jax.lax.scan(block, x, params["layers"])
            x = norm(g_final, x)
            hs.append(x)
            if u < a["passes"] - 1:
                gate = f32(params["exit_gate"])
                z = (x @ gate["kernel"])[:, 0] + gate["bias"][0]
                lams.append((1.0 / (1.0 + jnp.exp(-z)))[None])
    return jnp.stack(hs), jnp.concatenate(lams)


def sequence_logits(a, params, ids):
    import jax
    import jax.numpy as jnp

    h, lam = pass_states(a, params, ids)
    chosen = exit_pass(lam, a["exit_threshold"])
    h_exit = jnp.take_along_axis(h, chosen[None, :, None], axis=0)[0]
    with jax.default_matmul_precision("highest"):
        return h_exit @ params["lm_head"]["kernel"].astype(jnp.float32)


def layer_params(a):
    """Weights of one layer that take part in a matrix product."""
    return flops.attention_params(a) \
        + 3 * a["hidden_size"] * a["intermediate_size"]


def matmul_params(a):
    """Weights a token MEETS in a matrix product: the stack's U times (the
    passes share them; a token does not), the head once. The embedding
    lookup does not count."""
    return a["passes"] * a["num_layers"] * layer_params(a) \
        + a["hidden_size"] * a["vocab_size"]


def train_flops_per_token(a, seq):
    """U x the layers' 6 x parameters and attention terms, the head once."""
    return 6 * matmul_params(a) \
        + a["passes"] * flops.attention_train_flops(a, seq)


def kv_bytes_per_token(a, itemsize=2):
    """K and V of every (pass, layer) pair, all heads."""
    return kv_rows(a) * 2 * a["num_kv_heads"] * a["head_dim"] * itemsize


def decode_step_bytes(a, ctx_tokens, itemsize=2):
    """Bytes a decode step cannot avoid reading, whoever implements it: the
    layers' weights once a PASS (a pass needs the last pass's output, so no
    weight read serves two), the head once, and every cached token of the
    live contexts at every (pass, layer) row. The new rows' writes, the
    norms' scales and the activations are not counted: a floor."""
    return matmul_params(a) * itemsize \
        + ctx_tokens * kv_bytes_per_token(a, itemsize)
