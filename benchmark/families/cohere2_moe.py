"""``model_type: cohere2_moe`` (CohereLabs/command-a-plus-05-2026), the
LANGUAGE model — the equations of ISSUE 53 in plain ``jax.numpy``, for ONE
CHIP'S SHARE of an expert-parallel deployment: the router is as wide as
published, the chip holds some of its experts, and what the others would
have added is left out (``benchmark/README.md``, "What ``reduced`` may
hold"). Points the published ``config.json`` does not pin are marked (A)
and listed under ``assumed`` in the configuration's file.

Every layer is ONE parallel block under ONE norm; ``layer_types[l]`` says
which of two attentions it has. With x a token's row (h wide) and ``s <= t``
the positions of its sequence:

1. ``n = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g``: LayerNorm
   with a scale and NO bias.
2. ``q, k, v = n W_q, n W_k, n W_v`` [H, D], [KVH, D], [KVH, D]; no biases
   (``attention_bias`` false), no QK-norm (``use_qk_norm`` false).
3. ``sliding_attention``: q and k rotated, all D dims, theta ``rope_theta``,
   INTERLEAVED pairs (2i, 2i + 1) (``position_embedding_type: rope_gptj``);
   token t sees s iff ``0 <= t - s < sliding_window`` (A: the edge).
   ``full_attention``: NO positional term (A: from the model's description;
   the config has no key for it); t sees every ``s <= t``.
4. ``a = softmax(q k^T / sqrt(D)) v``, query head j reads KV head ``j //
   (H / KVH)``; ``attn = a W_o``.
5. ``s = sigmoid(n W_r)`` over all ``num_experts`` in float32; T = its
   ``num_experts_per_tok`` largest (ties to the lower id, ``lax.top_k``'s
   rule) (A); ``w_e = s_e / sum_T s`` (``norm_topk_prob``).
6. ``E(n) = (silu(n W_g) * (n W_u)) W_d``, every expert ``intermediate_size``
   wide (A: the config has no key of its own for an expert's width).
   ``ffn = sum_{e in T, held here} w_e E_e(n) + (1 / num_shared_experts)
   sum_j S_j(n)``: the shared experts AVERAGED
   (``shared_expert_combination_strategy``) and their mean ADDED to the
   routed sum (A: not a mean of routed and shared).
7. ``x' = x + attn + ffn`` (``use_parallel_block``).
8. ``logits = logit_scale x LayerNorm(x_L) Emb^T`` (tied embedding).

Departures from the publication, each a line:

* weights are seeded noise, in the program's tree;
* the program stores q and k in SPLIT-HALF rotary layout, the published
  checkpoint interleaved: :func:`to_interleaved` is the column permutation
  between them (what ingestion applies the inverse of), and the rotation
  here is the published, interleaved one on the permuted columns;
* the program stores the four shared experts as ONE GLU ``num_shared_experts
  x intermediate_size`` wide; here they are four, cut from its columns
  (w_gate, w_up) and rows (w_down);
* the vision tower is not run: text ids stand where its tokens would.

Plain, and independent of ``parallel/moe.py``, ``ops/`` and ``inference/``:
no cache, no kernel, no batching. What it does for MEMORY changes no
equation: a layer runs ``ROW_BLOCK`` rows at a time (first every row's key
and value, then each block's norm, attention, experts and sum), attention a
block of ``Q_BLOCK`` query rows at a time against all keys (a windowed layer
masks, it does not slice), the experts one at a time and cast to float32 one
at a time, an attention matrix ``W_SLAB`` rows at a time: a 50 k prompt fits
beside the 9.5 GB of bf16 weights, and a short one in the 2.4 GB a serving
engine leaves (the whole float32 copy of ONE layer is 4.6 GB)."""
import math

import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import reference as ref

Q_BLOCK = 16        # queries a block of the reference's attention takes
ROW_BLOCK = 2048    # rows a block of a layer takes (norm, q, experts, ...)
W_SLAB = 1024       # rows of an attention matrix cast to float32 at a time
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def arch(hf):
    held = hf["num_experts"]
    cut = hf.get("reduced", {}).get("num_experts")
    kinds = tuple(KINDS[t] for t in hf["layer_types"])
    if len(kinds) != hf["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(kinds)} entries for "
                         f"{hf['num_hidden_layers']} layers")
    return {"hidden_size": hf["hidden_size"],
            # ONE routed expert's width (what moe_roofline's expert_work
            # reads), and one shared expert's
            "intermediate_size": hf["intermediate_size"],
            "num_layers": hf["num_hidden_layers"], "num_dense_layers": 0,
            "layer_kinds": kinds,
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "rotary_dim": hf["head_dim"],
            "rope_theta": float(hf["rope_theta"]),
            "sliding_window": hf["sliding_window"],
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["layer_norm_eps"],
            "logit_scale": float(hf["logit_scale"]),
            # the ROUTER's width is the published count; the key itself
            # counts the experts held here (ids 0 onward)
            "num_experts": cut["published"] if cut else held,
            "experts_held": held, "first_expert_held": 0,
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "num_shared_experts": hf["num_shared_experts"],
            "norm_topk_prob": hf["norm_topk_prob"]}


def period_of(kinds):
    """The shortest prefix of ``kinds`` that, repeated, gives all of it."""
    return next(kinds[:p] for p in range(1, len(kinds) + 1)
                if len(kinds) % p == 0
                and kinds == kinds[:p] * (len(kinds) // p))


def program_widths(hf):
    a = arch(hf)
    period = tuple((a["sliding_window"], "rope") if kind == "sliding"
                   else (None, "none") for kind in period_of(a["layer_kinds"]))
    return {"hidden_size": a["hidden_size"],
            "intermediate_size": a["intermediate_size"],
            "moe_intermediate_size": None,
            "num_layers": a["num_layers"], "num_heads": a["num_heads"],
            "num_kv_heads": a["num_kv_heads"], "head_dim": a["head_dim"],
            "vocab_size": a["vocab_size"], "rope_theta": a["rope_theta"],
            "rotary_pct": float(hf["rotary_pct"]),
            "rms_norm_eps": a["norm_eps"], "norm_type": "layernorm",
            "norm_bias": False, "use_bias": bool(hf["attention_bias"]),
            "qk_norm": bool(hf["use_qk_norm"]), "qk_head_norm": False,
            "parallel_block": bool(hf["use_parallel_block"]),
            "shared_block_norm": True,
            "tie_embeddings": bool(hf["tie_word_embeddings"]),
            "logit_scale": a["logit_scale"], "attn_period": period,
            "sliding_window": None,
            "first_k_dense_replace": hf["first_k_dense_replace"],
            "num_experts": a["num_experts"],
            "experts_held": a["experts_held"],
            "first_expert_held": a["first_expert_held"],
            "num_experts_per_tok": a["num_experts_per_tok"],
            "norm_topk_prob": a["norm_topk_prob"],
            "scoring_func": hf["expert_selection_fn"],
            "topk_method": "greedy", "mlp_type": "glu", "activation": "silu",
            "n_shared_experts": a["num_shared_experts"],
            "shared_expert_combine":
                hf["shared_expert_combination_strategy"]}


# ------------------------------------------------------------------ pieces
def layer_norm(scale, x, eps):
    """LayerNorm with a scale and no bias."""
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


def to_interleaved(t):
    """The columns of a head stored split-half (pair i = columns i and i +
    D / 2, the program's layout) in the published INTERLEAVED order (pair i
    = columns 2i and 2i + 1). t: [..., D]."""
    d = t.shape[-1]
    return jnp.swapaxes(t.reshape(*t.shape[:-1], 2, d // 2), -1, -2) \
        .reshape(t.shape)


def rope_interleaved(a, x, positions):
    """x [S, H, D] rotated over all D in interleaved pairs (2i, 2i + 1),
    pair i at ``theta^(-2i / D)`` (rope_gptj)."""
    d = x.shape[-1]
    inv = 1.0 / (a["rope_theta"] ** (jnp.arange(0, d, 2) / d))      # [D/2]
    ang = positions[:, None].astype(jnp.float32) * inv           # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def in_blocks(fn, rows, block):
    """``fn`` over ``rows`` [S, ...] a block of ``block`` rows at a time
    (the last padded with zeros and cut off again)."""
    s = rows.shape[0]
    if s <= block:
        return fn(rows)
    n = -(-s // block)
    padded = jnp.pad(rows, ((0, n * block - s),) + ((0, 0),) * (rows.ndim - 1))
    out = jax.lax.map(fn, padded.reshape(n, block, *rows.shape[1:]))
    return out.reshape(n * block, *out.shape[2:])[:s]


def project(x, w, slab):
    """``x @ float32(w)`` with w [a, b] cast ``slab`` rows of it at a time
    (a sum over slabs of the contraction; whole where w has no more)."""
    rows = w.shape[0]
    if rows <= slab or rows % slab:
        return x @ w.astype(jnp.float32)

    def step(acc, i):
        w_i = jax.lax.dynamic_slice_in_dim(w, i * slab, slab, 0)
        x_i = jax.lax.dynamic_slice_in_dim(x, i * slab, slab, 1)
        return acc + x_i @ w_i.astype(jnp.float32), None

    acc, _ = jax.lax.scan(step, jnp.zeros((x.shape[0], w.shape[1]),
                                          jnp.float32),
                          jnp.arange(rows // slab))
    return acc


def keys_and_values(a, p, n, pos, kind):
    """The rows every later query attends to, of the normed rows n [R, h] at
    positions ``pos`` [R]: k [R, KVH, D] (rotated on a sliding layer) and
    v."""
    hk, d = a["num_kv_heads"], a["head_dim"]
    k = project(n, p["wk"], W_SLAB).reshape(-1, hk, d)
    v = project(n, p["wv"], W_SLAB).reshape(-1, hk, d)
    if kind == "sliding":
        k = rope_interleaved(a, to_interleaved(k), pos)
    return k, v


def attention(a, p, n, pos, k, v, kind):
    """One layer's attention of the normed rows n [R, h] at positions
    ``pos`` [R] over the sequence's keys and values k, v [S, KVH, D]
    (position = index), of ``kind`` ``sliding`` | ``full``; p: the layer's
    ``attn`` leaves as stored. -> [R, h]."""
    h, hk, d = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    keys = jnp.arange(k.shape[0])
    q = project(n, p["wq"], W_SLAB).reshape(-1, h, d)
    if kind == "sliding":
        q = rope_interleaved(a, to_interleaved(q), pos)

    def block(rows):
        """``rows`` [Q, 1 + H x D]: the queries' positions and heads."""
        at = rows[:, 0].astype(jnp.int32)
        q_b = rows[:, 1:].reshape(-1, hk, h // hk, d)
        scores = jnp.einsum("qkgd,skd->kgqs", q_b, k) / math.sqrt(d)
        seen = at[:, None] >= keys[None, :]
        if kind == "sliding":
            seen &= at[:, None] - keys[None, :] < a["sliding_window"]
        scores = jnp.where(seen, scores, -jnp.inf)
        w = jnp.exp(scores - scores.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        return jnp.einsum("kgqs,skd->qkgd", w, v).reshape(-1, h * d)

    # (a position rides as a float32 column: exact up to 2^24)
    heads = in_blocks(block, jnp.concatenate(
        [pos[:, None].astype(jnp.float32), q.reshape(-1, h * d)], axis=1),
        Q_BLOCK)
    return project(heads, p["wo"], W_SLAB)


def router(a, w_r, n):
    """Gates [S, E] (a token's top-k weights at its experts, 0 elsewhere)
    and each token's relative gap between its k-th and (k+1)-th score:
    where that is within the served precision's rounding, the served top-k
    SET may differ and the outputs legitimately with it."""
    k = a["num_experts_per_tok"]
    scores = jax.nn.sigmoid(n @ w_r)
    top, idx = jax.lax.top_k(scores, k + 1)
    gap = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
    top, idx = top[:, :k], idx[:, :k]
    if a["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(idx, a["num_experts"]) * top[..., None]).sum(1)
    return gates, gap


def ffn(a, moe, layer, n):
    """The routed experts HELD HERE (``moe``'s stacked leaves [L, held, .,
    .], read at ``layer`` one expert at a time) and the averaged shared
    experts, over the normed rows n [R, h]. -> (out [R, h], gaps [R])."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    first, held = a["first_expert_held"], moe["w_up"].shape[1]
    f, n_shared = a["intermediate_size"], a["num_shared_experts"]
    gates, gap = router(a, f32(moe["router"][layer]), n)

    def shared(acc, j):
        """+ shared expert j, its three matrices cut from the one wide GLU
        (one at a time, as the routed ones: four at once are 2 GB)."""
        cut = {k: jax.lax.dynamic_slice_in_dim(
            moe["shared"][k][layer], j * f, f, 1) for k in ("w_gate", "w_up")}
        cut["w_down"] = jax.lax.dynamic_slice_in_dim(
            moe["shared"]["w_down"][layer], j * f, f, 0)
        return acc + ref.swiglu({k: f32(w) for k, w in cut.items()}, n), None

    def one(acc, e):
        y = ref.swiglu({k: f32(moe[k][layer, e])
                        for k in ("w_gate", "w_up", "w_down")}, n)
        return acc + gates[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), jnp.arange(held))
    total, _ = jax.lax.scan(shared, jnp.zeros_like(n), jnp.arange(n_shared))
    return out + total / n_shared, gap


def layer(a, layers, l, x, kind):
    """Layer ``l`` (of ``kind``) over one sequence x [S, h]: two passes a
    block of ``ROW_BLOCK`` rows at a time, first every row's key and value,
    then each block's norm, attention over all keys, experts and sum.
    -> (x' [S, h], router gaps [S])."""
    s = x.shape[0]
    scale = layers["attn_norm"]["scale"][l].astype(jnp.float32)
    p = {k: w[l] for k, w in layers["attn"].items()}
    norm = lambda rows: layer_norm(scale, rows, a["norm_eps"])  # noqa: E731
    at = lambda idx: x[jnp.minimum(idx, s - 1)]  # noqa: E731 (a pad: cut off)

    def kv_rows(idx):
        k, v = keys_and_values(a, p, norm(at(idx)), idx, kind)
        return jnp.stack([k, v], axis=1)

    kv = in_blocks(kv_rows, jnp.arange(s), ROW_BLOCK)
    k, v = kv[:, 0], kv[:, 1]

    def block(idx):
        rows = at(idx)
        n = norm(rows)
        y, gap = ffn(a, layers["moe"], l, n)
        out = rows + attention(a, p, n, idx, k, v, kind) + y
        return jnp.concatenate([out, gap[:, None]], axis=-1)

    both = in_blocks(block, jnp.arange(s), ROW_BLOCK)
    return both[:, :-1], both[:, -1]


def _walk(a, params, ids):
    """-> (logits [S, V], router gaps [L, S]). The layers are walked in
    Python (their kinds are static), each leaf cast to float32 as it is
    used: no stack ever whole, an attention matrix a slab at a time."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"]
        x = f32(emb[ids])
        gaps = []
        for l, kind in enumerate(a["layer_kinds"]):
            x, gap = layer(a, params["layers"], l, x, kind)
            gaps.append(gap)
        h = layer_norm(f32(params["final_norm"]["scale"]), x, a["norm_eps"])
        # one plain product: the TPU compiler fuses the cast into it and
        # sinks a caller's row slice through it (benchmark.parity keeps the
        # last rows only)
        logits = a["logit_scale"] * (h @ f32(emb).T)
    return logits, jnp.stack(gaps)


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def router_gaps(a, params, ids):
    """[L, S]: per layer and position, :func:`router`'s relative gap
    between the k-th and (k+1)-th score in THIS forward (float32, highest):
    what a parity check counts its near-ties from."""
    return _walk(a, params, ids)[1]


# ------------------------------------------------------------------- FLOPs
def matmul_params(a):
    """Weights a token meets in a matrix product: attention, the router, its
    OWN ``num_experts_per_tok`` experts, the shared experts, and the tied
    output head."""
    d = a["hidden_size"]
    mlp = d * a["num_experts"] + 3 * d * a["intermediate_size"] * (
        a["num_experts_per_tok"] + a["num_shared_experts"])
    return a["num_layers"] * (flops.attention_params(a) + mlp) \
        + d * a["vocab_size"]


def attention_pairs(a, seq):
    """(query, key) pairs one sequence's attention scores, summed over the
    layers: a windowed layer's query sees ``min(position + 1,
    sliding_window)`` keys, a full layer's all before it."""
    return sum(flops.causal_pairs(
        seq, a["sliding_window"] if kind == "sliding" else None)
        for kind in a["layer_kinds"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met plus attention's two products over the pairs
    (``4 x head_dim`` per pair and head forward, three times that with the
    backward), for the WHOLE expert layer (a token's ``num_experts_per_tok``
    experts wherever they lie). The training path does not run this model;
    the count is the family's contract."""
    return 6 * matmul_params(a) + 3 * 4 * a["head_dim"] * a["num_heads"] \
        * attention_pairs(a, seq) / seq
