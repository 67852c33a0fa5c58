"""``model_type: brumby`` (manifestai/Brumby-14B-Base) — the equations of
ISSUE 49 in plain ``jax.numpy``: the Qwen3-14B block with its softmax
attention replaced by power retention of degree 2 (arXiv:2507.04239). Points
the published ``config.json`` does not pin are marked (A) and listed under
``assumed`` in the configuration's file.

L alike layers, hidden d, H query heads and HK key-value heads of D (query
head ``i`` reads head ``i // (H / HK)``), SwiGLU of width f, RMSNorm at
``rms_norm_eps``, full rotary embedding at ``rope_theta``, no bias but the
gate's, an untied head. Per layer, with ``h = RMSNorm(x)``:

    q_i = RoPE(RMSNorm_D(h Wq)_i)   k_j likewise   v_j = (h Wv)_j   (A: QK-norm)
    gam = log sigmoid(h Wg + bg)    [HK], one gate a key-value head
    A_tu = exp(Gam_t - Gam_u) (s q_t . k_u)^p   for u <= t, else 0
           Gam the running sum of gam, p = 2 (A), s = D^-1/2 (A)
    y_t  = sum_u A_tu v_u / (sum_u A_tu + eps)          eps 1e-6 (A)
    x += [y_0 .. y_{H-1}] Wo;   x += SwiGLU(RMSNorm(x))

This is the ATTENTION form: the whole sequence at once, a block of query
rows at a time, no state, no feature map, no chunks, no cache. The program
computes the same function as a recurrence over ``S_t = exp(gam) S_{t-1} +
phi(k_t) v_t^T`` (``ops/retention.py``); the two share nothing but the
parameter tree: ``attn`` has ``wq wk wv wo``, ``q_norm`` / ``k_norm`` (one
``[D]`` scale each), ``g_proj`` and ``g_bias``.
"""
import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import reference as ref

Q_BLOCK = 128       # queries a block of the reference's retention takes
DEGREE = 2          # (A) the config has no key for the power
EPS = 1e-6          # (A) the normaliser's floor
HEAD_CHUNKS = 8     # the head's product, a slice of the vocabulary at a time
ROW_BLOCK = 1024    # rows of the MLP's intermediates held at once
HEAD_ROWS = 256     # rows of logits a block of the head's product writes


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
            "sliding_window": None,
            "norm_eps": hf["rms_norm_eps"],
            "degree": DEGREE, "scale": head_dim ** -0.5, "eps": EPS}


def program_widths(hf):
    a = arch(hf)
    return {**{k: a[k] for k in (
        "hidden_size", "intermediate_size", "num_layers", "num_heads",
        "num_kv_heads", "head_dim", "vocab_size", "rope_theta")},
        "rms_norm_eps": a["norm_eps"], "retention_degree": a["degree"],
        "attn_scale": None,
        "qk_head_norm": True, "num_kv_layers": 0,
        "tie_embeddings": hf["tie_word_embeddings"]}


def log_sigmoid(x):
    return -jnp.logaddexp(0.0, -x)


def retention(a, p, x, weight=None):
    """Power retention over one sequence x [S, d], the attention form; the
    [H, Q_BLOCK, S] weights of one block of queries are all that is held.
    ``weight(A)``: a test's way into the weights (None: as they are)."""
    s, h, hk, d = (x.shape[0], a["num_heads"], a["num_kv_heads"],
                   a["head_dim"])
    pos = jnp.arange(s)
    head_norm = lambda t, g: ref.rms_norm(g, t, a["norm_eps"])  # noqa: E731
    q = ref.rope(a, head_norm((x @ p["wq"]).reshape(s, h, d), p["q_norm"]),
                 pos)
    k = ref.rope(a, head_norm((x @ p["wk"]).reshape(s, hk, d), p["k_norm"]),
                 pos)
    k = jnp.repeat(k, h // hk, axis=1)
    v = jnp.repeat((x @ p["wv"]).reshape(s, hk, d), h // hk, axis=1)
    gam = log_sigmoid(x @ p["g_proj"] + p["g_bias"])             # [S, HK]
    run = jnp.repeat(jnp.cumsum(gam, axis=0), h // hk, axis=1).T  # [H, S]

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        at = jnp.minimum(rows, s - 1)
        scores = jnp.einsum("qhd,khd->hqk", q[at], k) * a["scale"]
        seen = (rows[:, None] >= pos[None, :])[None]
        w = scores ** a["degree"] * jnp.exp(jnp.where(
            seen, run[:, at, None] - run[:, None, :], -jnp.inf))
        if weight is not None:
            w = weight(w)
        return jnp.einsum("hqk,khd->qhd", w, v) \
            / (w.sum(-1).T[:, :, None] + a["eps"])

    n_blocks = -(-s // Q_BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * Q_BLOCK)
    return out.reshape(n_blocks * Q_BLOCK, -1)[:s] @ p["wo"]


def mlp(p, x):
    """SwiGLU over x [S, d], ``ROW_BLOCK`` rows at a time: the [S, f]
    intermediates of a long sequence (0.5 GB each at 7.6 k tokens) are never
    held whole beside the logits."""
    s = x.shape[0]
    n = -(-s // ROW_BLOCK)
    rows = jnp.pad(x, ((0, n * ROW_BLOCK - s), (0, 0)))
    out = jax.lax.map(lambda xb: ref.swiglu(p, xb),
                      rows.reshape(n, ROW_BLOCK, -1))
    return out.reshape(n * ROW_BLOCK, -1)[:s]


def head(kernel, x):
    """``x @ kernel`` in float32, ``HEAD_ROWS`` rows and a slice of the
    vocabulary at a time: neither the head in float32 (3.1 GB at the
    published widths) nor a second copy of the logits in another layout is
    ever held beside a program that fills the chip."""
    s, (d, v) = x.shape[0], kernel.shape
    n = HEAD_CHUNKS if v % HEAD_CHUNKS == 0 else 1
    width = v // n
    blocks = -(-s // HEAD_ROWS)
    rows = jnp.pad(x, ((0, blocks * HEAD_ROWS - s), (0, 0)))

    def block(xb):
        def part(i, out):
            w = jax.lax.dynamic_slice(kernel, (0, i * width), (d, width))
            return jax.lax.dynamic_update_slice(
                out, xb @ w.astype(jnp.float32), (0, i * width))

        return jax.lax.fori_loop(
            0, n, part, jnp.zeros((HEAD_ROWS, v), jnp.float32))

    out = jax.lax.map(block, rows.reshape(blocks, HEAD_ROWS, d))
    return out.reshape(blocks * HEAD_ROWS, v)[:s]


def sequence_logits(a, params, ids):
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(jnp.float32), t)
    norm = lambda p, x: ref.rms_norm(p, x, a["norm_eps"])  # noqa: E731

    def block(x, p):
        p = f32(p)
        x = x + retention(a, p["attn"], norm(p["attn_norm"], x))
        return x + mlp(p["mlp"], norm(p["mlp_norm"], x)), None

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"][ids])
        x, _ = jax.lax.scan(block, x, params["layers"])
        x = norm(f32(params["final_norm"]), x)
        return head(params["lm_head"]["kernel"], x)


# ------------------------------------------------------- FLOPs and bytes
def features(a):
    """Distinct products of the symmetric square of a key: D (D + 1) / 2."""
    return a["head_dim"] * (a["head_dim"] + 1) // 2


def retention_state_bytes(a, itemsize=4):
    """Bytes of ONE sequence's state in ONE layer at its least: ``S``
    [features, D] and ``z`` [features] a key-value head. A program may hold
    more (its layout's padding), never less."""
    return a["num_kv_heads"] * features(a) * (a["head_dim"] + 1) * itemsize


def retention_row_bytes(a, itemsize=2):
    """Bytes of one row into and out of the recurrence in one layer: q, k,
    v and the gates in, y out."""
    d = a["head_dim"]
    return (2 * a["num_heads"] + 2 * a["num_kv_heads"]) * d * itemsize \
        + a["num_kv_heads"] * 4


def retention_chunk_flops(a, rows, first_piece):
    """FLOPs of ONE piece of ``rows`` consecutive tokens in ONE layer that
    no chunking can avoid: the quadratic part over the causal half (scores
    and weighted values, ``4 D`` a pair and query head), ``phi(Q) S`` and
    ``phi(Q) z`` only where the piece has a predecessor (``first_piece``
    false), the state's update always."""
    d, f = a["head_dim"], features(a)
    pairs = rows * (rows + 1) / 2
    quad = 4 * d * a["num_heads"] * pairs
    read = 0 if first_piece else 2 * rows * a["num_heads"] * f * (d + 1)
    write = 2 * rows * a["num_kv_heads"] * f * (d + 1)
    return quad + read + write


def matmul_params(a):
    """Weights a token meets in a matrix product: the projections (the
    gate's too), the MLP, the head."""
    d = a["hidden_size"]
    layer = flops.attention_params(a) + d * a["num_kv_heads"] \
        + 3 * d * a["intermediate_size"]
    return a["num_layers"] * layer + d * a["vocab_size"]


def train_flops_per_token(a, seq):
    """6 per matmul weight met and three times the recurrence's own FLOPs a
    token and layer (the state's update and the read-out of every query
    head). The training path does not run this model; the count is the
    family's contract."""
    del seq     # the recurrence costs a token the same at any length
    f, d = features(a), a["head_dim"]
    step = 2 * (a["num_heads"] + a["num_kv_heads"]) * f * (d + 1)
    return 6 * matmul_params(a) + 3 * step * a["num_layers"]


# --------------------------- the kind of layer it has: recurrent state
# (``benchmark.reference.layer_kind``; the counts below stood in
# ``metrics/ret_{share_pct,decode_roofline,chunk_roofline}.py`` until PR 62)
def layer_kinds():
    """Every layer's power retention is RECURRENT STATE. Its share counts
    the ``ret_proj`` (the q, k, v and output projections with their norms
    and rotary), ``ret_gate`` (the gate's projection) and ``ret_scan`` (the
    recurrence: the in-place decode step, the chunked form's pieces) scopes;
    the one-token rows' step is what lies under ``ret_scan`` in a decode
    forward (``S`` and ``z`` in their dtype and the program's own layout a
    slot-layer, ``engine.state_stats()``; the rows' expanded features are
    not counted), its pieces the record's ``ret_pieces``; the chunked form's
    pieces lie under ``ret_chunk``."""
    return {"recurrent_state": {
        "share_scopes": ("ret_proj", "ret_gate", "ret_scan"),
        "step_scopes": ("ret_scan",),
        "step_pieces": "ret_pieces",
        "chunk_scopes": ("ret_chunk",),
        "chunk_work": chunk_work}}


def pieces_of(record, layers):
    """``(rows, pieces, first)`` of the chunks of two tokens or more in the
    forward a ``round`` record launched: rows through EACH layer (``ret_rows
    - decode_rows``: a one-token chunk is a decode row), pieces
    (``ret_pieces``, summed over the layers, a one-token chunk one piece,
    less ``decode_rows`` x the layers) and those of them that start a
    sequence (``ret_first``) in ONE layer. None where the record lacks a
    count."""
    rows, pieces = record.get("ret_rows"), record.get("ret_pieces")
    ones = record.get("decode_rows")
    if rows is None or pieces is None or ones is None:
        return None
    return (rows - ones, pieces // layers - ones,
            record.get("ret_first", 0) // layers)


def pieces_work(a, rows, pieces, first, layers):
    """``(FLOPs, bytes)`` of one forward's pieces in all ``layers``, what no
    chunking can avoid: ``pieces`` pieces of ``rows / pieces`` rows each
    (taken as equal in rows, the least their quadratic part can cost: it is
    convex in a piece's rows), ``first`` of them with no predecessor: the
    FLOPs of ``retention_chunk_flops`` (the quadratic part over the causal
    half, ``phi(Q) S`` only for a piece with a predecessor, the state's
    update always), the rows in and out (``retention_row_bytes``) and every
    piece's state, read where it has a predecessor and written always, at
    its LEAST size (``retention_state_bytes``: the distinct products, not
    the program's layout)."""
    each = rows / pieces
    fl = (first * retention_chunk_flops(a, each, True)
          + (pieces - first) * retention_chunk_flops(a, each, False))
    state = retention_state_bytes(a)
    by = rows * retention_row_bytes(a) + (2 * pieces - first) * state
    return layers * fl, layers * by


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
