"""``model_type: solar_open2`` (upstage/Solar-Open2-250B) — the equations of
ISSUE 55 in plain ``jax.numpy``, for ONE CHIP'S SHARE of an expert-parallel
deployment (``benchmark/README.md``, "What ``reduced`` may hold": the router
is as wide as published, the chip holds some of its experts, what the others
would have added is left out). Points the published ``config.json`` does not
pin are marked (A) and listed under ``assumed`` in the configuration's file.

``num_hidden_layers`` layers, each a MIXER and then an EXPERT BLOCK: ``x <- x
+ mixer(RMSNorm(x))``, ``x <- x + experts(RMSNorm(x))``; after the last,
RMSNorm and an untied head. Layer ``l`` is softmax attention where ``l`` is in
``gqa_layers``, the gated delta rule elsewhere. No positional encoding
anywhere (``use_rope`` false): the delta-rule layers carry order. The PROGRAM
walks the same stack as a pattern of two characters a layer (``*E`` | ``KE``:
``program_widths``' ``layer_pattern``, ``num_layers`` = 2 x
``num_hidden_layers``); this file walks layers.

*Gated delta rule* (``described_as``: "gated delta-rule linear (neg.
eigenvalues, conv4)"; the delta attention of the Kimi Linear technical report
and its public ``fla`` implementation, A). H = ``linear_attn_config.
num_heads`` heads of D = ``linear_attn_config.head_dim`` for key and value
alike, K = ``short_conv_kernel_size``, rank R = D (``kda_use_full_proj``
false), no bias anywhere (A):

1. ``[q | k | v] = y W_qkv``; each channel ``c_t <- silu(sum_j w_j c_{t-(K-1)
   +j})``, depthwise, causal, zeros before the first token;
2. ``q <- q / sqrt(|q|^2 + 1e-6) D^-1/2``, ``k <- k / sqrt(|k|^2 + 1e-6)``, a
   head at a time (A: the eps);
3. ``g = -exp(A_log)[h] softplus(y W_fa W_fb + dt_bias)`` [H, D]: the log of
   the decay a key CHANNEL; ``beta = 2 sigmoid(y W_b)`` [H] (x 2:
   ``kda_allow_neg_eigval``);
4. ``S <- diag(exp g) S``; ``S <- S + beta k (v - S^T k)^T``; ``o = S^T q``,
   from ``S = 0`` [H, D, D]: a ``lax.scan`` over TOKENS, one state, no
   chunks, no cache;
5. ``o <- o / sqrt(mean o^2 + eps) w_o`` a head, times ``sigmoid(y W_ga
   W_gb)``; out ``o W_o``.

*Attention:* grouped-query causal softmax at ``head_dim^-1/2``, no bias, no
rotary; its output times ``sigmoid(y W_g)`` elementwise (``use_gqa_gate``; A:
the gate's form) before ``W_o``.

*Experts:* ``s = sigmoid(y W_r)`` over ALL the router's experts; the
``num_experts_per_tok`` largest of ``s + bias`` are chosen (no group limit;
ties to the lower id, A); weights = ``s`` at the chosen, divided by their sum
(+ 1e-20) where ``norm_topk_prob``, times ``routed_scaling_factor``. Expert
e: ``(silu(y G_e) * (y U_e)) V_e``. Plus the shared expert, the same form,
for every token. Only the experts HELD (ids ``first_expert_held`` onward, as
many as the tree's leaves have) are computed. ``intermediate_size`` is the
width of a dense MLP no layer has (``first_k_dense_replace`` 0).
"""
import jax
import jax.numpy as jnp

from benchmark import reference as ref

Q_BLOCK = 128       # queries a block of the reference's attention takes
L2_EPS = 1e-6       # (A) the floor under a head's squared norm
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def layer_pattern(hf):
    """The program's pattern: two characters a published layer."""
    return "".join("*E" if l in hf["gqa_layers"] else "KE"
                   for l in range(hf["num_hidden_layers"]))


def arch(hf):
    held = hf["n_routed_experts"]
    cut = hf.get("reduced", {}).get("n_routed_experts")
    lin = hf["linear_attn_config"]
    assert all(l < hf["num_hidden_layers"] for l in hf["gqa_layers"]), hf
    return {"hidden_size": hf["hidden_size"],
            # ONE routed expert's width; the shared expert's beside it
            "intermediate_size": hf["moe_intermediate_size"],
            "shared_intermediate_size":
                hf["n_shared_experts"] * hf["moe_intermediate_size"],
            # published layers: each has a mixer AND an expert block
            "num_layers": hf["num_hidden_layers"],
            "gqa_layers": tuple(hf["gqa_layers"]),
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"],
            "attn_gate": hf["use_gqa_gate"],
            "kda_num_heads": lin["num_heads"],
            "kda_head_dim": lin["head_dim"],
            "conv_kernel": lin["short_conv_kernel_size"],
            "beta_scale": 2.0 if hf["kda_allow_neg_eigval"] else 1.0,
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            # the ROUTER's width is the published count; the key itself
            # counts the experts held here (this chip's: ids 0 onward)
            "num_experts": cut["published"] if cut else held,
            "experts_held": held, "first_expert_held": 0,
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "routed_scaling_factor": hf["routed_scaling_factor"]}


def program_widths(hf):
    a = arch(hf)
    assert not hf["use_rope"] and not hf["first_k_dense_replace"] \
        and not hf["kda_use_full_proj"], hf
    return {"hidden_size": hf["hidden_size"],
            # the program counts pattern characters, two a published layer
            "num_layers": 2 * hf["num_hidden_layers"],
            "layer_pattern": layer_pattern(hf),
            "intermediate_size": hf["intermediate_size"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "pos_embed": "none",
            "attn_out_gate": hf["use_gqa_gate"],
            "vocab_size": hf["vocab_size"],
            "rms_norm_eps": hf["rms_norm_eps"],
            "kda_num_heads": a["kda_num_heads"],
            "kda_head_dim": a["kda_head_dim"],
            "kda_conv_kernel": a["conv_kernel"],
            # kda_use_full_proj false: the pairs' rank is a head's width
            "kda_gate_rank": a["kda_head_dim"],
            "kda_beta_scale": a["beta_scale"],
            "activation": "silu", "mlp_type": "glu",
            "moe_intermediate_size": hf["moe_intermediate_size"],
            "n_shared_experts": hf["n_shared_experts"],
            "num_experts": a["num_experts"],
            "experts_held": a["experts_held"],
            "first_expert_held": a["first_expert_held"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "routed_scaling_factor": float(hf["routed_scaling_factor"]),
            "tie_embeddings": hf["tie_word_embeddings"]}


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _unit(x):
    return x / jnp.sqrt(jnp.square(x).sum(-1, keepdims=True) + L2_EPS)


# --------------------------------------------------------- gated delta rule
def delta_rule(a, p, x):
    """One delta-rule mixer over one sequence x [S, d] (already normed): the
    recurrence token by token from a zero state."""
    s, h, d, kw = (x.shape[0], a["kda_num_heads"], a["kda_head_dim"],
                   a["conv_kernel"])
    heads = lambda t: t.reshape(s, h, d)                     # noqa: E731
    before = jnp.pad(x @ p["qkv_proj"], ((kw - 1, 0), (0, 0)))
    qkv = _silu(sum(p["conv_w"][j] * before[j:j + s] for j in range(kw)))
    q, k, v = (heads(qkv[:, i * h * d:(i + 1) * h * d]) for i in range(3))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * jnp.logaddexp(        # softplus
        heads((x @ p["f_a"]) @ p["f_b"]) + p["dt_bias"].reshape(h, d), 0.0)
    beta = a["beta_scale"] * _sigmoid(x @ p["b_proj"])        # [S, H]

    def token(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        held = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - held)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.square(o).mean(-1, keepdims=True) + a["norm_eps"])
    o = o * p["o_norm"]["scale"] * _sigmoid(heads((x @ p["g_a"]) @ p["g_b"]))
    return o.reshape(s, h * d) @ p["o_proj"]


# ---------------------------------------------------------------- attention
def attention(a, p, x):
    """Grouped-query causal attention over one sequence x [S, d], no
    positions, its output gated; the [H, Q_BLOCK, S] scores of one block of
    queries are all that is held."""
    s, h, hk, d = (x.shape[0], a["num_heads"], a["num_kv_heads"],
                   a["head_dim"])
    pos = jnp.arange(s)
    q = (x @ p["wq"]).reshape(s, h, d)
    k = jnp.repeat((x @ p["wk"]).reshape(s, hk, d), h // hk, axis=1)
    v = jnp.repeat((x @ p["wv"]).reshape(s, hk, d), h // hk, axis=1)

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        qb = q[jnp.minimum(rows, s - 1)]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        scores = jnp.where((rows[:, None] >= pos[None, :])[None], scores,
                           -jnp.inf)
        w = jnp.exp(scores - scores.max(-1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v)

    n_blocks = -(-s // Q_BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    out = out.reshape(n_blocks * Q_BLOCK, -1)[:s]
    if a["attn_gate"]:
        out = out * _sigmoid(x @ p["w_g"])
    return out @ p["wo"]


# ------------------------------------------------------------------ experts
def router(a, p, x):
    """Gates [S, E] over the router's WHOLE width (a token's weights at its
    chosen experts, 0 elsewhere) and the token's relative near-tie gap [S]:
    between the k-th and the (k+1)-th BIASED score, which is what the
    choice compares."""
    k, e = a["num_experts_per_tok"], a["num_experts"]
    scores = _sigmoid(x @ p["router"])
    top, idx = jax.lax.top_k(scores + p["router_bias"], k + 1)
    gaps = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    w = jnp.take_along_axis(scores, idx[:, :k], axis=-1)
    if a["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * a["routed_scaling_factor"]
    return (jax.nn.one_hot(idx[:, :k], e) * w[..., None]).sum(1), gaps


def experts(a, p, x, stacks, layer):
    """x [S, d] float32; ``p`` the layer's ``moe`` subtree (float32) without
    its expert matrices; those are ``stacks`` [L x held, ., .] AS STORED:
    held expert ``i`` of this layer (the router's id ``first_expert_held +
    i``) is row ``layer * held + i``, cut out and cast one at a time."""
    gates, gaps = router(a, p, x)
    held, first = a["experts_held"], a["first_expert_held"]

    def one(i, acc):
        w = {k: jax.lax.dynamic_index_in_dim(
            stacks[k], layer * held + i, keepdims=False).astype(jnp.float32)
            for k in EXPERT_LEAVES}
        g = jax.lax.dynamic_index_in_dim(gates, first + i, axis=1,
                                         keepdims=False)
        return acc + g[:, None] * ref.swiglu(w, x)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    if "shared" in p:
        out = out + ref.swiglu(p["shared"], x)
    return out, gaps


# --------------------------------------------------------------------- walk
def _walk(a, params, ids):
    """-> (logits [S, V], the routers' gaps [L, S]). Layer by layer, each
    kind of mixer reading the next layer of ITS stack, cast to float32 as it
    is used."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    at = lambda tree, j: f32(jax.tree_util.tree_map(  # noqa: E731
        lambda w: w[j], tree))
    norm = lambda q, y: ref.rms_norm(q, y, a["norm_eps"])  # noqa: E731
    moe = params["layers"]["moe"]
    stacks = {k: moe[k].reshape(-1, *moe[k].shape[2:]) for k in EXPERT_LEAVES}
    rest = {**params["layers"], "moe": {k: w for k, w in moe.items()
                                        if k not in EXPERT_LEAVES}}
    seen, gaps = {"attn": 0, "kda": 0}, []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids].astype(jnp.float32)
        for l in range(a["num_layers"]):
            if l in a["gqa_layers"]:
                p = at(params["attn_layers"], seen["attn"])
                seen["attn"] += 1
                x = x + attention(a, p["attn"], norm(p["attn_norm"], x))
            else:
                p = at(params["kda_layers"], seen["kda"])
                seen["kda"] += 1
                x = x + delta_rule(a, p, norm(p["norm"], x))
            p = at(rest, l)
            y, g = experts(a, p["moe"], norm(p["mlp_norm"], x), stacks, l)
            x = x + y
            gaps.append(g)
        h = norm(f32(params["final_norm"]), x)
        logits = h @ params["lm_head"]["kernel"].astype(jnp.float32)
    return logits, jnp.stack(gaps)


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def router_gaps(a, params, ids):
    """[L, S]: per layer and position the relative gap between the k-th and
    the (k+1)-th biased router score in THIS forward (float32, highest);
    ``benchmark.parity`` counts those under the served precision's
    rounding."""
    return _walk(a, params, ids)[1]


# ------------------------------------------------------- FLOPs and bytes
def layer_counts(a):
    """``(attention layers, delta-rule layers)``."""
    return len(a["gqa_layers"]), a["num_layers"] - len(a["gqa_layers"])


def kda_step_flops(a):
    """FLOPs of the recurrence itself for ONE row in ONE delta-rule layer,
    by the sequential form, which no chunking undercuts: per element of the
    state [H, D, D] the decay's multiply (1), ``k^T S`` (2), the rank-one
    write (2) and the read-out (2): 7 x H x D x D (the projections, the
    convolution, the norms and the gates are not the recurrence's)."""
    return 7 * a["kda_num_heads"] * a["kda_head_dim"] ** 2


def kda_row_bytes(a, itemsize=4):
    """Bytes of one row into and out of the recurrence in one layer: q, k,
    v and the log-decay [H, D] each and beta [H] in, o [H, D] out (float32:
    what the norms and the gates hand over)."""
    hd = a["kda_num_heads"] * a["kda_head_dim"]
    return (5 * hd + a["kda_num_heads"]) * itemsize


def kda_state_bytes(a, itemsize=4):
    """Bytes of ONE sequence's state in ONE delta-rule layer: ``S`` [H, D,
    D] (the convolution's tail beside it is the engine's to count)."""
    return a["kda_num_heads"] * a["kda_head_dim"] ** 2 * itemsize


def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers."""
    d = a["hidden_size"]
    n_attn, n_kda = layer_counts(a)
    hd, r = a["kda_num_heads"] * a["kda_head_dim"], a["kda_head_dim"]
    kda_w = 4 * d * hd + 2 * (d * r + r * hd) + d * a["kda_num_heads"]
    q = a["num_heads"] * a["head_dim"]
    attn_w = d * a["head_dim"] * (2 * a["num_heads"] + 2 * a["num_kv_heads"])\
        + (d * q if a["attn_gate"] else 0)
    moe_w = d * a["num_experts"] + 3 * d * (
        a["intermediate_size"] * a["num_experts_per_tok"]
        + a["shared_intermediate_size"])
    return (n_kda * kda_w + n_attn * attn_w + a["num_layers"] * moe_w
            + d * a["vocab_size"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met, attention's two products over the causal
    pairs in the attention layers, and three times the recurrence's own
    FLOPs in the delta-rule layers, for the WHOLE expert block (a token's
    ``num_experts_per_tok`` experts wherever they lie). The training path
    does not run this model; the count is the family's contract."""
    n_attn, n_kda = layer_counts(a)
    pairs = seq * (seq + 1) // 2
    attn = 3 * 4 * a["head_dim"] * a["num_heads"] * n_attn * pairs / seq
    return 6 * matmul_params(a) + attn + 3 * kda_step_flops(a) * n_kda


# --------------------------- the kind of layer it has: recurrent state
# (``benchmark.reference.layer_kind``; the counts below stood in
# ``metrics/kda_{share_pct,decode_roofline,chunk_roofline}.py`` until PR 62)
# the state step's custom call: ``name="kda_state_step"`` (ops/kda.py)
STEP_KERNELS = (("kda_state_step", "kda_step"),)


def layer_kinds():
    """The delta-rule layers are RECURRENT STATE. Their share counts the
    ``kda_proj`` (the projections in and out), ``kda_conv`` (the depthwise
    convolution and its tail), ``kda_gate`` (the decay, beta, the L2 norms,
    the gated output norm) and ``kda_scan`` (the recurrence: ``kda_step``,
    the in-place state step, and ``kda_chunk``, the chunked form's pieces,
    inside it) scopes, the state step's Pallas call by its name; the
    one-token rows' step is what lies under ``kda_conv`` and ``kda_step`` in
    a decode forward (the float32 state and the convolution's tail a
    slot-layer, ``engine.state_stats()``), its pieces the record's
    ``kda_pieces``; the chunked form's pieces lie under ``kda_chunk``."""
    return {"recurrent_state": {
        "share_scopes": ("kda_proj", "kda_conv", "kda_gate", "kda_scan",
                         "kda_step", "kda_chunk"),
        "share_kernels": STEP_KERNELS,
        "step_scopes": ("kda_conv", "kda_step"),
        "step_kernels": STEP_KERNELS,
        "step_pieces": "kda_pieces",
        "chunk_scopes": ("kda_chunk",),
        "chunk_work": chunk_work}}


def pieces_of(record, layers):
    """``(rows, pieces, first)`` of the chunks of two tokens or more in the
    forward a ``round`` record launched: rows through EACH layer (``kda_rows
    - decode_rows``: a one-token chunk is a decode row), pieces
    (``kda_pieces``, summed over the layers, a one-token chunk one piece,
    less ``decode_rows`` x the layers) and those of them that start a
    sequence (``kda_first``) in ONE layer. None where the record lacks a
    count."""
    rows, pieces = record.get("kda_rows"), record.get("kda_pieces")
    ones = record.get("decode_rows")
    if rows is None or pieces is None or ones is None:
        return None
    return (rows - ones, pieces // layers - ones,
            record.get("kda_first", 0) // layers)


def pieces_work(a, rows, pieces, first, layers):
    """``(FLOPs, bytes)`` of one forward's pieces in all ``layers``, what no
    chunking can avoid: ``rows`` rows in ``pieces`` pieces, ``first`` of
    them with no predecessor: the recurrence's own FLOPs by the SEQUENTIAL
    form (``kda_step_flops`` a row and layer; a chunked form does more and
    reads lower), the rows in and out (``kda_row_bytes``) and every piece's
    state (``kda_state_bytes``), read where it has a predecessor and written
    always."""
    state = kda_state_bytes(a)
    return (layers * rows * kda_step_flops(a),
            layers * (rows * kda_row_bytes(a)
                      + (2 * pieces - first) * state))


def chunk_work(obs):
    """``record -> (FLOPs, bytes)`` of a forward's pieces (``None`` where it
    carried none), or ``None`` for an engine without a state pool."""
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    if not stats or not stats.get("layers"):
        return None
    a = arch(obs["config"])
    layers = stats["layers"]

    def work(record):
        rows, pieces, first = pieces_of(record, layers) or (0, 0, 0)
        if rows <= 0 or pieces <= 0:
            return None
        return pieces_work(a, rows, pieces, first, layers)
    return work
