"""``model_type: phi`` — parallel block ``x + attn(ln(x)) + mlp(ln(x))``
under ONE LayerNorm, partial rotary embedding (``partial_rotary_factor`` of
each head), two-matrix MLP with tanh-GELU, biases everywhere (HF
``modeling_phi``)."""
from benchmark import flops
from benchmark import reference as ref


def arch(hf):
    heads = hf["num_attention_heads"]
    head_dim = hf["hidden_size"] // heads
    rotary = int(head_dim * hf["partial_rotary_factor"])
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "num_heads": heads,
            "num_kv_heads": hf.get("num_key_value_heads") or heads,
            "head_dim": head_dim, "rotary_dim": rotary - rotary % 2,
            "vocab_size": hf["vocab_size"],
            "rope_theta": hf["rope_theta"],
            "sliding_window": None,
            "norm_eps": hf["layer_norm_eps"]}


def program_widths(hf):
    a = arch(hf)
    return {**{k: a[k] for k in (
        "hidden_size", "intermediate_size", "num_layers", "num_heads",
        "num_kv_heads", "head_dim", "vocab_size", "rotary_dim")},
        "sliding_window": None}


def sequence_logits(a, params, ids):
    import jax

    norm = lambda p, x: ref.layer_norm(p, x, a["norm_eps"])  # noqa: E731

    def block(p, x):
        y = norm(p["attn_norm"], x)
        m = p["mlp"]
        hidden = jax.nn.gelu(y @ m["fc1"] + m["b1"], approximate=True)
        return x + ref.attention(a, p["attn"], y) + hidden @ m["fc2"] + m["b2"]

    return ref.decoder_logits(params, ids, block, norm)


def matmul_params(a):
    mlp = 2 * a["hidden_size"] * a["intermediate_size"]
    return a["num_layers"] * (flops.attention_params(a) + mlp) \
        + a["hidden_size"] * a["vocab_size"]


def train_flops_per_token(a, seq):
    return 6 * matmul_params(a) + flops.attention_train_flops(a, seq)
