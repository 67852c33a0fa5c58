"""``model_type: nemotron_h`` (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) —
the equations of ISSUE 37 in plain ``jax.numpy``, for ONE CHIP'S SHARE of an
expert-parallel deployment (``benchmark/README.md``, "What ``reduced`` may
hold": the router is as wide as published, the chip holds some of its
experts, what the others would have added is left out). Points the published
``config.json`` does not pin are marked (A) and listed under ``assumed`` in
the configuration's file.

``hybrid_override_pattern`` has one character a layer, each layer ONE mixer:
``x <- x + mixer(RMSNorm(x))``; after the last, RMSNorm and an untied head.
No positional encoding anywhere (A: the published modeling code's attention
reads neither ``rope_theta`` nor ``partial_rotary_factor``).

*``M``, Mamba-2* (H = ``mamba_num_heads``, P = ``mamba_head_dim``, d_inner =
H x P, NOT ``expand`` x hidden; G = ``n_groups``; N = ``ssm_state_size``; K
= ``conv_kernel``; no projection bias, a convolution bias):

1. ``[z | xBC | dt] = y W_in``, widths d_inner | d_inner + 2 G N | H;
2. ``xBC_t <- silu(b + sum_j w_j * xBC_{t-(K-1)+j})``, depthwise, causal,
   zeros before the first token; split ``x | B | C``; head h reads group
   ``h // (H / G)``;
3. ``dt = softplus(dt + dt_bias)`` (``time_step_limit`` (0, inf): no
   clamp), ``A = -exp(A_log)``;
4. ``S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t`` from ``S = 0``; ``y_t = S_t
   C_t + D x_t``: a ``lax.scan`` over TOKENS, one state, no chunks, no cache;
5. ``y <- y * silu(z)``, RMS-normalised in G groups of d_inner / G, times a
   d_inner-wide weight; out ``y W_out``.

*``E``:* ``s = sigmoid(y W_r)`` over ALL the router's experts; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` are chosen
(``n_group`` 1: no group limit; ties to the lower id, A); weights = ``s`` at
the chosen, divided by their sum (+ 1e-20), times ``routed_scaling_factor``.
Expert e: ``relu(y U_e)^2 V_e``. Plus the shared expert, the same form at
``moe_shared_expert_intermediate_size``, for every token. Only the experts
HELD (ids ``first_expert_held`` onward, as many as the tree's leaves have)
are computed.

*``*``:* grouped-query causal softmax attention at ``head_dim^-1/2``, no
bias, no rotary.
"""
import math

import jax
import jax.numpy as jnp

from benchmark import reference as ref

Q_BLOCK = 128       # queries a block of the reference's attention takes
KINDS = {"M": "mamba_layers", "E": "layers", "*": "attn_layers"}


def arch(hf):
    held = hf["n_routed_experts"]
    cut = hf.get("reduced", {}).get("n_routed_experts")
    pattern = hf["hybrid_override_pattern"]
    assert len(pattern) == hf["num_hidden_layers"], (pattern, hf)
    return {"hidden_size": hf["hidden_size"],
            # ONE routed expert's width; the shared expert's beside it
            "intermediate_size": hf["moe_intermediate_size"],
            "shared_intermediate_size":
                hf["moe_shared_expert_intermediate_size"],
            "num_layers": hf["num_hidden_layers"], "pattern": pattern,
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"],
            "mamba_num_heads": hf["mamba_num_heads"],
            "mamba_head_dim": hf["mamba_head_dim"],
            "ssm_state_size": hf["ssm_state_size"],
            "n_groups": hf["n_groups"], "conv_kernel": hf["conv_kernel"],
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["layer_norm_epsilon"],
            # the ROUTER's width is the published count; the key itself
            # counts the experts held here (this chip's: ids 0 onward)
            "num_experts": cut["published"] if cut else held,
            "experts_held": held, "first_expert_held": 0,
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "routed_scaling_factor": hf["routed_scaling_factor"]}


def program_widths(hf):
    a = arch(hf)
    return {"hidden_size": hf["hidden_size"],
            "num_layers": hf["num_hidden_layers"],
            "layer_pattern": hf["hybrid_override_pattern"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "pos_embed": "none",
            "vocab_size": hf["vocab_size"],
            "rms_norm_eps": hf["layer_norm_epsilon"],
            "mamba_num_heads": hf["mamba_num_heads"],
            "mamba_head_dim": hf["mamba_head_dim"],
            "ssm_state_size": hf["ssm_state_size"],
            "ssm_n_groups": hf["n_groups"],
            "ssm_conv_kernel": hf["conv_kernel"],
            "ssm_chunk_size": hf["chunk_size"],
            "time_step_min": hf["time_step_min"],
            "time_step_max": hf["time_step_max"],
            "time_step_floor": hf["time_step_floor"],
            "activation": hf["mlp_hidden_act"], "mlp_type": "mlp",
            "moe_intermediate_size": hf["moe_intermediate_size"],
            "shared_expert_intermediate_size":
                hf["moe_shared_expert_intermediate_size"],
            "n_shared_experts": hf["n_shared_experts"],
            "num_experts": a["num_experts"],
            "experts_held": a["experts_held"],
            "first_expert_held": a["first_expert_held"],
            "num_experts_per_tok": hf["num_experts_per_tok"],
            "n_group": hf["n_group"], "topk_group": hf["topk_group"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "routed_scaling_factor": float(hf["routed_scaling_factor"]),
            "tie_embeddings": hf["tie_word_embeddings"]}


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


# ------------------------------------------------------------------ Mamba-2
def mamba(a, p, x):
    """One Mamba-2 mixer over one sequence x [S, d] (already normed): the
    recurrence token by token from a zero state."""
    s = x.shape[0]
    h, pd, g, n, k = (a["mamba_num_heads"], a["mamba_head_dim"],
                      a["n_groups"], a["ssm_state_size"], a["conv_kernel"])
    di = h * pd
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * g * n],
                  zxbcdt[:, 2 * di + 2 * g * n:])
    before = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = _silu(p["conv_b"] + sum(p["conv_w"][j] * before[j:j + s]
                                  for j in range(k)))
    xs = xbc[:, :di].reshape(s, h, pd)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), h // g, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), h // g, axis=1)
    dt = jnp.logaddexp(dt + p["dt_bias"], 0.0)              # softplus
    neg_a = -jnp.exp(p["A_log"])

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * neg_a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) \
            + p["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((h, pd, n), jnp.float32),
                        (xs, b, c, dt))
    y = (y.reshape(s, di) * _silu(z)).reshape(s, g, di // g)
    y = y / jnp.sqrt(jnp.square(y).mean(-1, keepdims=True) + a["norm_eps"])
    return (y.reshape(s, di) * p["gate_norm"]["scale"]) @ p["out_proj"]


# ---------------------------------------------------------------- attention
def attention(a, p, x):
    """Grouped-query causal attention over one sequence x [S, d], no
    positions; the [H, Q_BLOCK, S] scores of one block of queries are all
    that is held."""
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
    return out.reshape(n_blocks * Q_BLOCK, -1)[:s] @ p["wo"]


# ------------------------------------------------------------------ experts
def router(a, p, x):
    """Gates [S, E] over the router's WHOLE width (a token's weights at its
    chosen experts, 0 elsewhere) and the token's relative near-tie gap [S]:
    between the k-th and the (k+1)-th BIASED score, which is what the
    choice compares."""
    k, e = a["num_experts_per_tok"], a["num_experts"]
    scores = 1.0 / (1.0 + jnp.exp(-(x @ p["router"])))
    top, idx = jax.lax.top_k(scores + p["router_bias"], k + 1)
    gaps = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    w = jnp.take_along_axis(scores, idx[:, :k], axis=-1)
    if a["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * a["routed_scaling_factor"]
    return (jax.nn.one_hot(idx[:, :k], e) * w[..., None]).sum(1), gaps


def relu2_mlp(up, down, x):
    return jnp.square(jnp.maximum(x @ up, 0.0)) @ down


EXPERT_LEAVES = ("w_up", "w_down")


def experts(a, p, x, stacks, layer):
    """x [S, d] float32; ``p`` the layer's ``moe`` subtree (float32) without
    its expert matrices; those are ``stacks`` [L_moe * held, ., .] AS STORED:
    held expert ``i`` of this layer (the router's id ``first_expert_held +
    i``) is row ``layer * held + i``, cut out and cast one at a time."""
    gates, gaps = router(a, p, x)
    held, first = a["experts_held"], a["first_expert_held"]

    def one(i, acc):
        up, down = (jax.lax.dynamic_index_in_dim(
            stacks[k], layer * held + i, keepdims=False).astype(jnp.float32)
            for k in EXPERT_LEAVES)
        g = jax.lax.dynamic_index_in_dim(gates, first + i, axis=1,
                                         keepdims=False)
        return acc + g[:, None] * relu2_mlp(up, down, x)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    if "shared" in p:
        out = out + relu2_mlp(p["shared"]["fc1"], p["shared"]["fc2"], x)
    return out, gaps


# --------------------------------------------------------------------- walk
def _walk(a, params, ids):
    """-> (logits [S, V], the routers' gaps [L_moe, S]). The pattern is
    walked layer by layer, each kind reading the next layer of ITS stack,
    cast to float32 as it is used."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    at = lambda tree, j: f32(jax.tree_util.tree_map(  # noqa: E731
        lambda w: w[j], tree))
    norm = lambda q, y: ref.rms_norm(q, y, a["norm_eps"])  # noqa: E731
    moe = params["layers"]["moe"]
    stacks = {k: moe[k].reshape(-1, *moe[k].shape[2:]) for k in EXPERT_LEAVES}
    rest = {**params["layers"], "moe": {k: w for k, w in moe.items()
                                        if k not in EXPERT_LEAVES}}
    seen, gaps = dict.fromkeys(KINDS, 0), []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids].astype(jnp.float32)
        for kind in a["pattern"]:
            j = seen[kind]
            seen[kind] += 1
            if kind == "M":
                p = at(params["mamba_layers"], j)
                x = x + mamba(a, p, norm(p["norm"], x))
            elif kind == "*":
                p = at(params["attn_layers"], j)
                x = x + attention(a, p["attn"], norm(p["attn_norm"], x))
            else:
                p = at(rest, j)
                y, g = experts(a, p["moe"], norm(p["mlp_norm"], x), stacks, j)
                x = x + y
                gaps.append(g)
        h = norm(f32(params["final_norm"]), x)
        logits = h @ params["lm_head"]["kernel"].astype(jnp.float32)
    return logits, jnp.stack(gaps)


def sequence_logits(a, params, ids):
    return _walk(a, params, ids)[0]


def router_gaps(a, params, ids):
    """[L_moe, S]: per expert layer and position the relative gap between
    the k-th and the (k+1)-th biased router score in THIS forward (float32,
    highest); ``benchmark.parity`` counts those under the served
    precision's rounding."""
    return _walk(a, params, ids)[1]


# ------------------------------------------------------- FLOPs and bytes
def layer_counts(a):
    return {kind: a["pattern"].count(kind) for kind in KINDS}


def ssm_scan_flops(a):
    """FLOPs of the recurrence itself for ONE row in ONE Mamba layer: per
    element of the state [H, P, N] a decay multiply, the input's
    multiply-add and the output's multiply-add, 6 x H x P x N (the
    projections and the convolution are not the scan's)."""
    return 6 * a["mamba_num_heads"] * a["mamba_head_dim"] \
        * a["ssm_state_size"]


def ssm_row_bytes(a, itemsize=2):
    """Bytes of one row into and out of the scan in one Mamba layer: x, B
    and C after the convolution and dt in, y out."""
    di = a["mamba_num_heads"] * a["mamba_head_dim"]
    return (2 * di + 2 * a["n_groups"] * a["ssm_state_size"]) * itemsize \
        + a["mamba_num_heads"] * 4


def ssm_state_bytes(a, state_itemsize=4, conv_itemsize=2):
    """Bytes of ONE sequence's state in ONE Mamba layer: the SSM state [H,
    P, N] and the convolution's tail [K - 1, d_inner + 2 G N]."""
    di = a["mamba_num_heads"] * a["mamba_head_dim"]
    return (di * a["ssm_state_size"] * state_itemsize
            + (a["conv_kernel"] - 1)
            * (di + 2 * a["n_groups"] * a["ssm_state_size"]) * conv_itemsize)


def expert_work(a, touched, rows, itemsize=2):
    """``(FLOPs, bytes)`` of one forward's grouped GEMMs: ``rows`` (token,
    expert) rows in all its ``E`` layers, each through one expert's TWO
    ``hidden x intermediate`` matrices; ``touched`` expert-layers' weights
    read once, the rows' activations in and out."""
    d, f = a["hidden_size"], a["intermediate_size"]
    return (rows * 2 * 2 * d * f,
            (touched * 2 * d * f + 2 * rows * d) * itemsize)


def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers."""
    d = a["hidden_size"]
    di = a["mamba_num_heads"] * a["mamba_head_dim"]
    gn = a["n_groups"] * a["ssm_state_size"]
    n = layer_counts(a)
    mamba_w = d * (2 * di + 2 * gn + a["mamba_num_heads"]) + di * d
    attn_w = d * a["head_dim"] * (2 * a["num_heads"] + 2 * a["num_kv_heads"])
    moe_w = d * a["num_experts"] + 2 * d * (
        a["intermediate_size"] * a["num_experts_per_tok"]
        + a["shared_intermediate_size"])
    return (n["M"] * mamba_w + n["*"] * attn_w + n["E"] * moe_w
            + d * a["vocab_size"])


def train_flops_per_token(a, seq):
    """6 per matmul weight met, attention's two products over the causal
    pairs in the ``*`` layers, and three times the recurrence's own FLOPs in
    the ``M`` layers, for the WHOLE expert layer (a token's
    ``num_experts_per_tok`` experts wherever they lie). The training path
    does not run this model; the count is the family's contract."""
    n = layer_counts(a)
    pairs = seq * (seq + 1) // 2
    attn = 3 * 4 * a["head_dim"] * a["num_heads"] * n["*"] * pairs / seq
    return 6 * matmul_params(a) + attn + 3 * ssm_scan_flops(a) * n["M"]


# --------------------------- the kind of layer it has: recurrent state
# (``benchmark.reference.layer_kind``; the counts below stood in
# ``metrics/ssm_{share_pct,decode_roofline,chunk_roofline}.py`` until PR 62)
def layer_kinds():
    """The ``M`` layers are RECURRENT STATE. Their share counts the
    ``ssm_proj`` (in and out projections), ``ssm_conv`` (the depthwise
    convolution and its tail in the state pool), ``ssm_scan`` (the recurrence
    with its ``D`` skip: the in-place decode step, the chunked scan's pieces)
    and ``ssm_gate`` (the gate and its grouped norm) scopes; the one-token
    rows' step is what lies under ``ssm_conv`` and ``ssm_scan`` in a decode
    forward, its pieces the record's ``ssm_pieces``; the chunked scan's
    pieces lie under ``ssm_chunk`` (inside ``ssm_scan``)."""
    return {"recurrent_state": {
        "share_scopes": ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate"),
        "step_scopes": ("ssm_conv", "ssm_scan"),
        "step_pieces": "ssm_pieces",
        "slot_layer_bytes": slot_layer_bytes,
        "chunk_scopes": ("ssm_chunk",),
        "chunk_work": chunk_work}}


def slot_layer_bytes(obs):
    """Bytes of one sequence's state in ONE Mamba layer as the engine holds
    it (``engine.state_stats()``: the SSM state in its dtype and the
    convolution's tail), or None."""
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    layers = getattr(getattr(obs.get("engine"), "kv", None), "ssm", None)
    if not stats or layers is None:
        return None
    return stats["bytes_per_slot"] / layers.shape[0]


def pool_state_bytes(obs):
    """One sequence's SSM state in ONE Mamba layer as the engine holds it
    (``engine.kv.ssm``; the convolution's tail is ``ssm_conv``'s, not
    counted), or None."""
    pool = getattr(getattr(obs.get("engine"), "kv", None), "ssm", None)
    return None if pool is None \
        else math.prod(pool.shape[2:]) * pool.dtype.itemsize


def pieces_of(record, layers):
    """``(rows, pieces)`` of the chunks of two tokens or more in the forward
    a ``round`` record launched: rows through EACH Mamba layer (``ssm_rows -
    decode_rows``: a one-token chunk is a decode row), pieces summed over
    the ``layers`` of them (``ssm_pieces``, a one-token chunk one piece,
    less ``decode_rows`` x those layers). None where the record lacks a
    count."""
    rows, pieces = record.get("ssm_rows"), record.get("ssm_pieces")
    ones = record.get("decode_rows")
    if rows is None or pieces is None or ones is None:
        return None
    return rows - ones, pieces - ones * layers


def scan_work(a, rows, pieces, per_piece, layers):
    """``(FLOPs, bytes)`` of one forward's pieces, what no chunking can
    avoid: ``rows`` rows through each of ``layers`` Mamba layers (the
    recurrence's own FLOPs, ``ssm_scan_flops``, and the rows in and out of
    the scan, ``ssm_row_bytes``), ``pieces`` state pieces (summed over the
    layers already) of ``per_piece`` bytes each, read and written once. The
    quadratic form inside a piece spends more FLOPs than the recurrence
    needs and is not counted: a floor."""
    return (rows * layers * ssm_scan_flops(a),
            rows * layers * ssm_row_bytes(a) + 2 * pieces * per_piece)


def chunk_work(obs):
    """``record -> (FLOPs, bytes)`` of a forward's pieces (``None`` where it
    carried none), or ``None`` for an engine without a state pool."""
    per_piece = pool_state_bytes(obs)
    if not per_piece:
        return None
    a = arch(obs["config"])
    layers = layer_counts(a)["M"]

    def work(record):
        rows, pieces = pieces_of(record, layers) or (0, 0)
        if rows <= 0 or pieces <= 0:
            return None
        return scan_work(a, rows, pieces, per_piece, layers)
    return work
