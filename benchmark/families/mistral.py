"""``model_type: mistral`` — pre-norm sequential block, RMSNorm,
grouped-query attention with full rotary embedding and a sliding window,
SwiGLU MLP, no biases (Jiang et al. 2023, and HF ``modeling_mistral``)."""
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
            "sliding_window": hf.get("sliding_window"),
            "norm_eps": hf["rms_norm_eps"]}


def program_widths(hf):
    a = arch(hf)
    return {k: a[k] for k in (
        "hidden_size", "intermediate_size", "num_layers", "num_heads",
        "num_kv_heads", "head_dim", "vocab_size", "sliding_window")}


def sequence_logits(a, params, ids):
    norm = lambda p, x: ref.rms_norm(p, x, a["norm_eps"])  # noqa: E731

    def block(p, x):
        x = x + ref.attention(a, p["attn"], norm(p["attn_norm"], x))
        return x + ref.swiglu(p["mlp"], norm(p["mlp_norm"], x))

    return ref.decoder_logits(params, ids, block, norm)


def matmul_params(a):
    """Weights that take part in a matrix product per token (the embedding
    lookup does not; the output head does)."""
    mlp = 3 * a["hidden_size"] * a["intermediate_size"]
    return a["num_layers"] * (flops.attention_params(a) + mlp) \
        + a["hidden_size"] * a["vocab_size"]


def train_flops_per_token(a, seq):
    return 6 * matmul_params(a) + flops.attention_train_flops(a, seq)
