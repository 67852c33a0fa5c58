"""The plain reference: a decoder forward in straightforward ``jax.numpy``.

Independent of the code under test: no kernel, no cache, no batching, no
mesh, float32 with ``jax.default_matmul_precision("highest")``. The forward
of one model family is a file of its own, ``families/<model_type>.py``,
found by the ``model_type`` a configuration's file publishes
(``spec.Bench.family``); a later PR that brings a new family brings its
file and edits nothing here. A family's module gives:

* ``arch(hf)`` — the sizes its forward and its counts need, from the
  published ``config.json`` keys held in the configuration's file;
* ``program_widths(hf)`` — what the program's model config must say for
  those keys (``{attribute: value}``): the file is the yardstick, the
  program's preset is under test;
* ``sequence_logits(arch, params, ids)`` — logits ``[S, V]`` (float32) of
  one token sequence, from the program's parameter tree (``embed``,
  ``layers`` stacked over depth, ``final_norm``, ``lm_head``), which is the
  one thing the two share;
* ``train_flops_per_token(arch, seq)`` — forward + backward FLOPs a trained
  token needs (``benchmark.flops`` has the pieces).

A module MAY also say what layers of a KIND it has, so that the per-layer
readers of that kind read its cells and a later family lists no reader of its
own: ``layer_kinds()`` gives ``{kind: what it says}`` (``layer_kind`` below
asks; a family without the function, or without the kind, gives the readers
nothing to read and they return ``None``). The arithmetic of a kind's floors
(a layer's FLOPs and bytes, which record field counts its pieces) is the
family's; the loop over the traced rounds is the reader's. The kinds:

* ``"recurrent_state"`` (``metrics/state_*``) — ``share_scopes`` (+
  ``share_kernels``): the scopes its share of the busy time counts;
  ``step_scopes`` (+ ``step_kernels``) and ``step_pieces``: the scopes of
  the one-token rows' state step and the ``round`` record's field that
  counts the pieces it reads and writes; ``slot_layer_bytes(obs)``: one
  sequence's state in one layer as the engine holds it (without it:
  ``engine.state_stats()``'s ``bytes_per_slot / layers``); ``chunk_scopes``
  (+ ``chunk_kernels``): how the chunked form is found in the trace;
  ``chunk_work(obs)``: ``None``, or a function of a ``round`` record that
  gives ``(FLOPs, bytes)`` of the forward's pieces, ``None`` where the
  record carried none.
* ``"selection"`` (attention over keys chosen by a scoring pass;
  ``metrics/select_*``) — ``scopes`` and ``kernels``: everything of the
  layer in the trace; ``roles``: ``{"score" | "select" | "attend": the
  labels of that role}``; ``score``, ``prefill``, ``rows``: each ``{"scopes",
  "kernels", "work"}``, the labels a roofline asks the trace for and
  ``work(obs)``: ``None``, or a function ``(record, counted, seconds)`` of a
  traced round that launched a forward (its record, the FOLLOWING record,
  which holds what the device counted of it, and the seconds under each
  label inside its execution) that gives ``(least seconds, seconds taken)``
  or ``None``.

``KERNELS`` are ``((prefix of a custom call's name, label), ...)``
(``benchmark.scopes``).

This file holds what the families share (norms, rotary embedding, softmax
attention, the walk over stacked layers) and what decides ``correct`` from
a family's logits (greedy margins, the LM loss). Departures from the
publications: rotary pairs are split-half (the program's weight layout;
HF's is the same for the families here), weights are seeded noise.
"""
import math

BF16_EPS = 2.0 ** -8
FAMILY_API = ("arch", "program_widths", "sequence_logits",
              "train_flops_per_token")
LAYER_KINDS = ("recurrent_state", "selection")


def layer_kind(family, kind):
    """What ``family`` says of its layers of ``kind`` (one of
    ``LAYER_KINDS``), or ``None``: a family that has no such layer says
    nothing, and a reader of the kind then has nothing to read."""
    say = getattr(family, "layer_kinds", None)
    return say().get(kind) if callable(say) else None


def rms_norm(p, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


def layer_norm(p, x, eps):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rope(arch, x, positions):
    """x: [S, H, D]; rotates the first ``rotary_dim`` of every head."""
    import jax.numpy as jnp

    rd = arch["rotary_dim"]
    inv = 1.0 / (arch["rope_theta"] ** (jnp.arange(0, rd, 2) / rd))
    ang = positions[:, None].astype(jnp.float32) * inv       # [S, rd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rd:]], axis=-1)


def attention(arch, p, x):
    """Causal (windowed) softmax attention over one sequence, grouped-query
    where ``num_kv_heads < num_heads``, biases where the tree has them.
    x: [S, d]."""
    import jax.numpy as jnp

    s = x.shape[0]
    h, hk, d = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = jnp.arange(s)
    q = rope(arch, q.reshape(s, h, d), pos)
    k = rope(arch, k.reshape(s, hk, d), pos)
    v = v.reshape(s, hk, d)
    k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    seen = pos[:, None] >= pos[None, :]
    if arch["sliding_window"]:
        seen &= pos[:, None] - pos[None, :] < arch["sliding_window"]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    w = jnp.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    out = jnp.einsum("hqk,khd->qhd", w, v).reshape(s, h * d) @ p["wo"]
    return out + p["bo"] if "bo" in p else out


def swiglu(p, x):
    import jax.numpy as jnp

    g = x @ p["w_gate"]
    return (g / (1.0 + jnp.exp(-g)) * (x @ p["w_up"])) @ p["w_down"]


def decoder_logits(params, ids, block, final_norm):
    """Embed ``ids`` [S], walk the stacked layers with ``block(p, x)``,
    apply ``final_norm(p, x)`` and the output head. Layers are walked with
    ``lax.scan``, each cast to float32 as it is used, so only one layer is
    ever held in float32."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"][ids])
        x, _ = jax.lax.scan(lambda x, p: (block(f32(p), x), None), x,
                            params["layers"])
        x = final_norm(f32(params["final_norm"]), x)
        logits = x @ f32(params["lm_head"]["kernel"])
        if "bias" in params["lm_head"]:
            logits = logits + f32(params["lm_head"]["bias"])
    return logits


def greedy_margins(family, hf, params, prompt, emitted):
    """How far each emitted token's reference logit sits below the
    reference argmax's (0.0 = the same choice), in units of that row's
    logit standard deviation — logits, not tokens, because with random
    weights the largest logit changes on rounding. Copied in shape from
    ``chip_smoke.reference_greedy_margins``; the forward is the family's."""
    import jax
    import numpy as np

    arch = family.arch(hf)
    ids = np.asarray(list(prompt) + list(emitted), np.int32)
    logits = jax.jit(
        lambda p, i: family.sequence_logits(arch, p, i))(params, ids)
    rows = np.asarray(logits[len(prompt) - 1:-1], np.float32)
    picked = rows[np.arange(len(emitted)), np.asarray(emitted)]
    return ((rows.max(-1) - picked) / rows.std(-1)).tolist()


def lm_loss(family, hf, params, input_ids):
    """Mean next-token cross-entropy of a batch [B, S], one row at a time
    (the [S, S] scores and [S, V] logits of one row are all that is held)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    arch = family.arch(hf)

    def row_nll(p, ids):
        logits = family.sequence_logits(arch, p, ids)[:-1]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
        return (logz - gold).sum()

    f = jax.jit(row_nll)
    ids = np.asarray(input_ids, np.int32)
    total = sum(float(f(params, row)) for row in ids)
    return total / (ids.shape[0] * (ids.shape[1] - 1))
