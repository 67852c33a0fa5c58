"""``model_type: falcon_h1`` (tiiuae/Falcon-H1-34B-Instruct) — the equations of
ISSUE 63 in plain ``jax.numpy``. Points the published ``config.json`` does not
pin are marked (A) and listed under ``assumed`` in the configuration's file.

``num_hidden_layers`` layers, each attention heads AND a Mamba-2 mixer SIDE BY
SIDE behind one norm, then a SwiGLU MLP behind another (``mamba_use_mlp``;
``attn_layer_indices`` null: attention in every layer). Every multiplier is a
scalar of the config. ``x_0 = embedding_multiplier embed(id)``; for layer
input ``x``, ``u = RMSNorm_in(x)``:

*attention half* (H = ``num_attention_heads`` over KVH = ``num_key_value_heads``
heads of D = ``head_dim``; ``q_dim`` = H x D, not ``hidden_size``; no bias):
``q = (attention_in_multiplier u) W_q``, ``k = ((attention_in_multiplier u)
W_k) key_multiplier``, ``v`` likewise without the key's; q and k rotated over
the whole head at ``rope_theta`` (no scaling; the key's multiplier and the
rotation commute, A: the order); causal ``softmax(q k^T / sqrt(D)) v``; ``a =
(rows W_o) attention_out_multiplier``.

*Mamba-2 half* (Hm = ``mamba_n_heads`` heads of P = ``mamba_d_head``, d_ssm =
``mamba_d_ssm`` = Hm x P, NOT ``mamba_expand`` x hidden; G = ``mamba_n_groups``
groups of state N = ``mamba_d_state``; K = ``mamba_d_conv``; no projection
bias, a convolution bias):

1. ``[z | x | B | C | dt] = ((ssm_in_multiplier u) W_in) * mup_vector``,
   widths d_ssm | d_ssm | G N | G N | Hm, ``mup_vector`` = ``ssm_multipliers``
   [0..4] over those slices;
2. ``xBC_t <- silu(b + sum_j w_j * xBC_{t-(K-1)+j})`` over ``[x | B | C]``,
   depthwise, causal, zeros before the first token; head h reads group ``h //
   (Hm / G)``;
3. ``dt = softplus(dt + dt_bias)`` (no clamp, A: ``time_step_limit`` is not
   in the row), ``A = -exp(A_log)``;
4. ``S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t`` from ``S = 0``; ``y_t = S_t
   C_t + D x_t``: a ``lax.scan`` over TOKENS, one state, no chunks, no cache;
5. ``y <- y * silu(z)`` FIRST (``mamba_norm_before_gate`` false), then
   RMS-normalised in G groups of d_ssm / G (``mamba_rms_norm``) times a
   d_ssm-wide weight; ``m = (y W_out) ssm_out_multiplier``.

``x1 = x + a + m``: ONE sum, both halves read ``u``. Then ``x2 = x1 + ((up(v)
* silu(gate(v) mlp_multipliers[0])) W_down) mlp_multipliers[1]``, ``v =
RMSNorm_ff(x1)``. After the last layer RMSNorm and an untied head, ``logits =
(h W_head) lm_head_multiplier``.

The PROGRAM walks the same stack as a pattern of two characters a layer
(``HF``: ``program_widths``' ``layer_pattern``, ``num_layers`` = 2 x
``num_hidden_layers``); this file walks layers. Departures, none of them
another function: a matrix stored in bf16 is NOT cast to float32 (the
261,120-row head alone would be 5.3 GB beside 13.5 GiB of weights and pools):
:func:`matmul` splits the float32 rows into three bf16 terms that sum to them
exactly and multiplies each natively with float32 accumulation, the products
that precision "highest" makes for an operand with no lower terms; the
attention takes ``Q_BLOCK`` queries at a time; and the logits of a long
sequence come back UNEMBEDDED ON DEMAND (:class:`Logits`, as
``minicpm_sala``'s: ``[4608, 261120]`` float32 is 4.8 GB): the harness
slices the rows it compares.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as ref
from benchmark.families import nemotron_h as mamba2

Q_BLOCK = 128               # queries a block of the reference's attention
ROW_BLOCK = 2048            # rows of logits ``np.asarray`` brings at a time
# logits of no more bytes come back as a plain array: a ``Logits`` that leaves
# a ``jit`` takes the head out with it, a COPY of 2.49 GiB beside the engine
DENSE_BYTES = 1 << 30
# the leaves :func:`matmul` takes as stored; every other is cast to float32
MATRICES = ("wq", "wk", "wv", "wo", "in_proj", "out_proj", "w_gate", "w_up",
            "w_down")
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def arch(hf):
    assert hf["mamba_d_ssm"] == hf["mamba_n_heads"] * hf["mamba_d_head"], hf
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            # published layers: each has the two mixers AND an MLP
            "num_layers": hf["num_hidden_layers"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"],
            "rope_theta": float(hf["rope_theta"]),
            "rotary_dim": hf["head_dim"],
            # (the names ``nemotron_h``'s counts read)
            "mamba_num_heads": hf["mamba_n_heads"],
            "mamba_head_dim": hf["mamba_d_head"],
            "ssm_state_size": hf["mamba_d_state"],
            "n_groups": hf["mamba_n_groups"],
            "conv_kernel": hf["mamba_d_conv"],
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            **{k: float(hf[k]) for k in MULTIPLIERS},
            "ssm_multipliers": tuple(map(float, hf["ssm_multipliers"])),
            "mlp_multipliers": tuple(map(float, hf["mlp_multipliers"]))}


def program_widths(hf):
    assert hf["mamba_use_mlp"] and hf["mamba_rms_norm"] \
        and hf["mamba_conv_bias"] and not hf["mamba_norm_before_gate"] \
        and hf["attn_layer_indices"] is None and not hf["rope_scaling"] \
        and not (hf["attention_bias"] or hf["mlp_bias"]
                 or hf["mamba_proj_bias"] or hf["projectors_bias"]), hf
    a = arch(hf)
    return {"hidden_size": hf["hidden_size"],
            # the program counts pattern characters, two a published layer
            "num_layers": 2 * hf["num_hidden_layers"],
            "layer_pattern": "HF" * hf["num_hidden_layers"],
            "intermediate_size": hf["intermediate_size"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "pos_embed": "rope",
            "rope_theta": a["rope_theta"], "rotary_pct": 1.0,
            "vocab_size": hf["vocab_size"],
            "rms_norm_eps": hf["rms_norm_eps"],
            "mamba_num_heads": hf["mamba_n_heads"],
            "mamba_head_dim": hf["mamba_d_head"],
            "ssm_state_size": hf["mamba_d_state"],
            "ssm_n_groups": hf["mamba_n_groups"],
            "ssm_conv_kernel": hf["mamba_d_conv"],
            "ssm_chunk_size": hf["mamba_chunk_size"],
            "activation": hf["hidden_act"], "mlp_type": "glu",
            "embed_scale": a["embedding_multiplier"],
            "logit_scale": a["lm_head_multiplier"], "residual_scale": 1.0,
            # ModelConfig.mup's seven entries, in its order
            "mup": (a["attention_in_multiplier"],
                    a["attention_out_multiplier"], a["key_multiplier"],
                    a["ssm_in_multiplier"], a["ssm_out_multiplier"],
                    a["ssm_multipliers"], a["mlp_multipliers"]),
            "tie_embeddings": hf["tie_word_embeddings"]}


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def matmul(x, w):
    """``x @ w`` in float32 at the highest precision, ``w`` [in, out] as
    stored. A bf16 ``w`` is multiplied natively: ``x`` is three bf16 terms
    that sum to it exactly (24 bits of mantissa, 8 a term), each product
    exact and summed in float32."""
    if w.dtype != jnp.bfloat16:
        return x @ w.astype(jnp.float32)
    out, rest = 0.0, x
    for _ in range(3):
        term = rest.astype(jnp.bfloat16)
        out = out + jnp.dot(term, w, preferred_element_type=jnp.float32)
        rest = rest - term.astype(jnp.float32)
    return out


@jax.tree_util.register_pytree_node_class
class Logits:
    """``[S, V]`` float32 logits held as the normed hidden rows ``h`` [S, d]
    float32, the head ``w`` [d, V] as stored and ``lm_head_multiplier``: a
    row is unembedded when it is asked for (the module's docstring has
    why)."""

    def __init__(self, h, w, scale):
        self.h, self.w, self.scale = h, w, scale

    def tree_flatten(self):
        return (self.h, self.w, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    shape = property(lambda self: (self.h.shape[0], self.w.shape[1]))
    dtype = jnp.dtype(jnp.float32)

    def __len__(self):
        return self.h.shape[0]

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return matmul(self.h[rows], self.w) * self.scale

    def __array__(self, dtype=None, copy=None):
        out = np.concatenate([
            np.asarray(self[at:at + ROW_BLOCK])
            for at in range(0, len(self), ROW_BLOCK)])
        return out if dtype is None else out.astype(dtype)


# ---------------------------------------------------------------- attention
def attention(a, p, u):
    """The attention half over one sequence's normed rows u [S, d]; the [H,
    Q_BLOCK, S] scores of one block of queries are all that is held."""
    s, h, hk, d = (u.shape[0], a["num_heads"], a["num_kv_heads"],
                   a["head_dim"])
    pos = jnp.arange(s)
    u = a["attention_in_multiplier"] * u
    q = ref.rope(a, matmul(u, p["wq"]).reshape(s, h, d), pos)
    k = ref.rope(a, (matmul(u, p["wk"]) * a["key_multiplier"]
                     ).reshape(s, hk, d), pos)
    k = jnp.repeat(k, h // hk, axis=1)
    v = jnp.repeat(matmul(u, p["wv"]).reshape(s, hk, d), h // hk, axis=1)

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        qb = q[jnp.minimum(rows, s - 1)]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        scores = jnp.where((rows[:, None] >= pos[None, :])[None], scores,
                           -jnp.inf)
        w = jnp.exp(scores - scores.max(-1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v)

    n_blocks = -(-s // Q_BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    return matmul(out.reshape(n_blocks * Q_BLOCK, -1)[:s], p["wo"]) \
        * a["attention_out_multiplier"]


# ------------------------------------------------------------------ Mamba-2
def mup_vector(a):
    """[2 d_ssm + 2 G N + Hm]: ``ssm_multipliers`` over ``[z | x | B | C |
    dt]``."""
    di = a["mamba_num_heads"] * a["mamba_head_dim"]
    gn = a["n_groups"] * a["ssm_state_size"]
    return jnp.repeat(jnp.asarray(a["ssm_multipliers"], jnp.float32),
                      np.asarray((di, di, gn, gn, a["mamba_num_heads"])))


def mamba(a, p, u):
    """The Mamba-2 half over one sequence's normed rows u [S, d]: the
    recurrence token by token from a zero state."""
    s = u.shape[0]
    h, pd, g, n, k = (a["mamba_num_heads"], a["mamba_head_dim"],
                      a["n_groups"], a["ssm_state_size"], a["conv_kernel"])
    di = h * pd
    zxbcdt = matmul(a["ssm_in_multiplier"] * u, p["in_proj"]) * mup_vector(a)
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
    y = (y.reshape(s, di) * _silu(z)).reshape(s, g, di // g)   # gate FIRST
    y = y / jnp.sqrt(jnp.square(y).mean(-1, keepdims=True) + a["norm_eps"])
    return matmul(y.reshape(s, di) * p["gate_norm"]["scale"],
                  p["out_proj"]) * a["ssm_out_multiplier"]


def feed_forward(a, p, v):
    gate = matmul(v, p["w_gate"]) * a["mlp_multipliers"][0]
    return matmul(matmul(v, p["w_up"]) * _silu(gate), p["w_down"]) \
        * a["mlp_multipliers"][1]


# --------------------------------------------------------------------- walk
def sequence_logits(a, params, ids):
    """-> logits [S, V] float32, as :class:`Logits` where they pass
    ``DENSE_BYTES``. The layers are walked one by one, the small
    leaves cast to float32 as they are used, the matrices as stored
    (:func:`matmul`)."""
    def at(tree, j):
        return {k: at(v, j) if isinstance(v, dict) else
                v[j] if k in MATRICES else v[j].astype(jnp.float32)
                for k, v in tree.items()}

    norm = lambda q, y: ref.rms_norm(q, y, a["norm_eps"])      # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids].astype(jnp.float32) \
            * a["embedding_multiplier"]
        for j in range(a["num_layers"]):
            p = at(params["hybrid_layers"], j)
            u = norm(p["norm"], x)
            x = x + attention(a, p["attn"], u) + mamba(a, p["mamba"], u)
            p = at(params["ffn_layers"], j)
            x = x + feed_forward(a, p["mlp"], norm(p["mlp_norm"], x))
        h = norm({"scale": params["final_norm"]["scale"].astype(
            jnp.float32)}, x)
    logits = Logits(h, params["lm_head"]["kernel"], a["lm_head_multiplier"])
    small = 4 * h.shape[0] * a["vocab_size"] <= DENSE_BYTES
    return logits[:] if small else logits


# ------------------------------------------------------- FLOPs and bytes
# (the recurrence's own counts are ``nemotron_h``'s, at THIS model's sizes:
# ``arch`` gives them under the names those functions read)
ssm_scan_flops = mamba2.ssm_scan_flops
ssm_row_bytes = mamba2.ssm_row_bytes
ssm_state_bytes = mamba2.ssm_state_bytes


def matmul_params(a):
    """Weights a token meets in a matrix product, summed over the layers."""
    d = a["hidden_size"]
    di = a["mamba_num_heads"] * a["mamba_head_dim"]
    gn = a["n_groups"] * a["ssm_state_size"]
    mamba_w = d * (2 * di + 2 * gn + a["mamba_num_heads"]) + di * d
    attn_w = d * a["head_dim"] * (2 * a["num_heads"] + 2 * a["num_kv_heads"])
    return a["num_layers"] * (mamba_w + attn_w
                              + 3 * d * a["intermediate_size"]) \
        + d * a["vocab_size"]


def train_flops_per_token(a, seq):
    """6 per matmul weight met, attention's two products over the causal
    pairs and three times the recurrence's own FLOPs, in every layer. The
    training path does not run this model; the count is the family's
    contract."""
    pairs = seq * (seq + 1) // 2
    attn = 3 * 4 * a["head_dim"] * a["num_heads"] * a["num_layers"] \
        * pairs / seq
    return 6 * matmul_params(a) + attn \
        + 3 * ssm_scan_flops(a) * a["num_layers"]


# --------------------------- the kind of layer it has: recurrent state
def layer_kinds():
    """The Mamba-2 HALF of every layer is RECURRENT STATE, under the scopes
    ``nemotron_h``'s ``M`` layers run under (the attention half beside it
    runs under ``h1_attn``): the share counts ``ssm_proj``, ``ssm_conv``,
    ``ssm_scan`` and ``ssm_gate``; the one-token rows' step is what lies
    under ``ssm_conv`` and ``ssm_scan`` in a decode forward, its pieces the
    record's ``ssm_pieces``, a slot-layer's bytes the engine's own (4 MiB of
    float32 state + the convolution's tail here); the chunked scan's pieces
    lie under ``ssm_chunk``."""
    return {"recurrent_state": {
        "share_scopes": ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate"),
        "step_scopes": ("ssm_conv", "ssm_scan"),
        "step_pieces": "ssm_pieces",
        "slot_layer_bytes": mamba2.slot_layer_bytes,
        "chunk_scopes": ("ssm_chunk",),
        "chunk_work": chunk_work}}


def chunk_work(obs):
    """``record -> (FLOPs, bytes)`` of a forward's pieces (``None`` where it
    carried none), or ``None`` for an engine without a state pool:
    ``nemotron_h.scan_work`` over this model's layers, each of which has the
    mixer."""
    per_piece = mamba2.pool_state_bytes(obs)
    if not per_piece:
        return None
    a = arch(obs["config"])
    layers = a["num_layers"]

    def work(record):
        rows, pieces = mamba2.pieces_of(record, layers) or (0, 0)
        if rows <= 0 or pieces <= 0:
            return None
        return mamba2.scan_work(a, rows, pieces, per_piece, layers)
    return work
