"""The gated delta rule with a decay per key CHANNEL (Kimi delta attention,
the Kimi Linear technical report, Moonshot AI 2025) for the serving path: the
recurrence of one layer over a continuous batch, against a state that lives
per sequence SLOT and never grows with the context.

``h`` heads, a key of ``dk`` and a value of ``dv`` channels a head. Per token,
with ``q`` and ``k`` L2-normalised a head (``q`` scaled by ``dk^-1/2``), ``g``
the log of the decay a key channel (``<= 0``) and ``beta`` the write's
strength (up to 2: the transition ``I - beta k k^T`` may have an eigenvalue
of -1):

    S_t = (I - beta k k^T) diag(exp g) S_{t-1} + beta k v^T     [dk, dv], f32
    o_t = S_t^T q

Unlike Mamba-2's state (``ops/ssm.py``: a scalar decay a head) and power
retention's (``ops/retention.py``: a gated sum), the write SUBTRACTS what the
decayed state already holds for the key, ``k^T diag(exp g) S``, before it
adds: the step reads the state against the incoming key, and the chunked
form is a triangular solve, not a masked product.

The state pool (``inference/v2/kv_cache.BlockedKV.kda_s``) holds ``S`` itself
per layer and slot, ``[h, dk, dv]`` float32: the KEY's channels on the
sublanes, the value's on the lanes. The decay is then PER SUBLANE (a column
``[dk, 1]`` times the block), ``k^T S`` and ``S^T q`` are sums over sublanes
(whole-register adds) that come out as lane-dense rows, and the rank-one
write is a column times a row. Slot ``S`` (the last) is the sink padding
writes to. A piece whose first position is 0 starts from zeros, whatever its
slot held: the host resets nothing. The depthwise convolution before it and
its tail are Mamba-2's (``ops/ssm.conv_step``, on the TPU the in-place
kernel ``conv_tail_step``, / ``conv_pieces``), called with the layer's ``3 x
h x dk`` channels and no bias.

Two entries, as ``ops/ssm.py`` and ``ops/retention.py`` have:

* :func:`decode_step` — ONE token for each of ``[rows]`` slots, the state
  updated IN PLACE. On the TPU a Pallas kernel (``kda_state_step``) whose
  state block is the pool's own ``[layer, slot, heads]`` (scalar-prefetch
  indices, the pool aliased to the output): each state is read once and
  written once, and the four passes over it (decay, ``k^T S``, the write,
  ``S^T q``) run on the block while it is in VMEM. ``xla``: gather, update,
  scatter (the CPU tests' reference, and what the kernel is held against).
* :func:`chunked` — the pieces of the chunks of two tokens or more in one
  flat batch (``ragged.ssm_pieces``): inside a piece the WY form. With ``G``
  the running sum of ``g``, ``A = beta (K e^G)(K e^-G)^T`` strictly lower,
  ``T = (I + A)^-1``, ``W = T (beta K e^G)``, ``U = T (beta V)``: the piece's
  writes are ``U - W S``, its outputs ``(Q e^G) S`` and the causal ``(Q
  e^G)(K e^-G)^T`` part over them, and ``S <- e^{G_last} S + ...`` through
  the slot. ``e^-G`` alone overflows under a strong decay, so no exponent is
  ever taken by itself: pairs in the same SUB-BLOCK of a quarter piece take
  ``exp(G_t - G_s)`` directly, and a pair across sub-blocks is referred to
  the later one's start, ``exp(G_t - R) exp(R - G_s)``, both ``<= 1``. The
  decay is never clamped. Its pieces carry the scope ``kda_chunk`` inside
  ``kda_scan``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor.mfu import scope
from .ssm import _divisor, default_impl

HIGHEST = jax.lax.Precision.HIGHEST
# the floor under a head's squared norm: x / sqrt(|x|^2 + eps)
L2_EPS = 1e-6


def l2norm(x):
    """``x`` [..., d] float32 over its last axis."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


# ----------------------------------------------------------- the decode step
def _state_step_xla(pool, layer, slots, keep, q, k, v, g, beta):
    """``pool[layer, slots]`` one step on: gather, update, scatter (a
    scatter on the loop-carried pool is in place; the gather is a copy).
    ``keep`` [rows] float32 0/1: 0 starts the row from zeros. q, k, g [rows,
    h, dk]; v [rows, h, dv]; beta [rows, h], all float32. -> ``(S^T q
    [rows, h, dv], pool)``."""
    state = pool[layer, slots] * keep[:, None, None, None]
    state = state * jnp.exp(g)[..., None]
    held = jnp.einsum("rhkv,rhk->rhv", state, k, precision=HIGHEST)
    write = beta[..., None] * (v - held)
    new = state + k[..., None] * write[:, :, None, :]
    out = jnp.einsum("rhkv,rhk->rhv", new, q, precision=HIGHEST)
    return out, pool.at[layer, slots].set(new.astype(pool.dtype))


def _state_step_kernel(layer_ref, slots_ref, keep_ref, cols_ref, rows_ref,
                       st_ref, o_ref, out_ref, *, heads):
    """``heads`` heads of one row's state, ``[dk, dv]`` each: read once,
    written once. ``cols`` [dk, 3 x heads]: a head's decay, key and query
    down the sublanes (lane ``j``, ``heads + j``, ``2 heads + j``); ``rows``
    [2 x heads, dv]: ``beta v`` and ``beta`` along the lanes."""
    del layer_ref, slots_ref          # the BlockSpecs' own
    f32 = jnp.float32
    keep = keep_ref[pl.program_id(0)].astype(f32)
    for j in range(heads):
        decay = cols_ref[:, j:j + 1] * keep
        key = cols_ref[:, heads + j:heads + j + 1]
        query = cols_ref[:, 2 * heads + j:2 * heads + j + 1]
        state = st_ref[j].astype(f32) * decay
        held = jnp.sum(state * key, axis=0, keepdims=True)
        write = rows_ref[j:j + 1, :] - rows_ref[heads + j:heads + j + 1, :] \
            * held
        state = state + key * write
        out_ref[j] = state.astype(out_ref.dtype)
        o_ref[j:j + 1, :] = jnp.sum(state * query, axis=0, keepdims=True)


# heads a grid step of the decode kernel takes: a block of STEP_HEADS x [dk,
# dv] float32 (1 MiB at 16 x [128, 128]), in and out, double-buffered. The
# step's body is unrolled over them (a head's columns are static lane slices
# of ``cols``), so more heads a step are more code to lower at every set-up;
# fewer are more blocks, each with a fixed cost beside its bytes. By a sweep
# on the v5e (PERF.md section 6, PR 55).
STEP_HEADS = 16


def _state_step_pallas(pool, layer, slots, keep, q, k, v, g, beta,
                       interpret=False, heads=None):
    """The same step with the pool aliased to the output: block ``[layer,
    slots[row], heads]`` of the pool in, the same block out. The decay, the
    key and the query come TRANSPOSED, down the sublanes, as the state's key
    channels lie. Rows that share a slot (the sink) write it one after
    another; nobody reads it."""
    rows, h, dk, dv = (slots.shape[0], *pool.shape[2:])
    hb = heads or _divisor(h, STEP_HEADS)
    n = h // hb
    f32 = jnp.float32
    # [rows, n, dk, 3 hb]: decay | key | query, a head a lane
    cols = jnp.concatenate(
        [t.astype(f32).reshape(rows, n, hb, dk).swapaxes(2, 3)
         for t in (jnp.exp(g), k, q)], axis=-1)
    b = beta.astype(f32)[..., None]
    lanes = jnp.concatenate(
        [(b * v.astype(f32)).reshape(rows, n, hb, dv),
         jnp.broadcast_to(b, (rows, h, dv)).reshape(rows, n, hb, dv)], axis=2)
    row = lambda r, i, *_: (r, i, 0, 0)                     # noqa: E731
    state = lambda r, i, layer_ref, slots_ref, keep_ref: (  # noqa: E731
        layer_ref[0], slots_ref[r], i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(rows, n),
        in_specs=[pl.BlockSpec((None, None, dk, 3 * hb), row),
                  pl.BlockSpec((None, None, 2 * hb, dv), row),
                  pl.BlockSpec((None, None, hb, dk, dv), state)],
        out_specs=[pl.BlockSpec((None, None, hb, dv), row),
                   pl.BlockSpec((None, None, hb, dk, dv), state)])
    block = hb * dk * dv * pool.dtype.itemsize
    out, pool = pl.pallas_call(
        functools.partial(_state_step_kernel, heads=hb),
        out_shape=[jax.ShapeDtypeStruct((rows, n, hb, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch three: the pool is the 6th
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(8 * block + (8 << 20), 16 << 20),
                                 100 << 20)),
        interpret=interpret, name="kda_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      keep.astype(jnp.int32), cols, lanes, pool)
    return out.reshape(rows, h, dv), pool


STATE_STEPS = {
    "xla": _state_step_xla,
    "pallas": _state_step_pallas,
    "pallas_interpret": functools.partial(_state_step_pallas,
                                          interpret=True),
}


def decode_step(q, k, v, g, beta, pool, layer, slots, fresh, cfg, step=None):
    """One token for each row. ``q`` / ``k`` [rows, h, dk] as the
    convolution and the L2 norms give them (``q`` scaled), ``v`` [rows, h,
    dv], ``g`` [rows, h, dk] the log of the decay a key channel, ``beta``
    [rows, h]; ``pool`` the state pool, ``layer`` the layer, ``slots``
    [rows] each row's state slot (the sink for a row that is padding),
    ``fresh`` [rows] bool: the row is its sequence's first token. ``step``:
    one of :data:`STATE_STEPS` (None: by platform; the serving forwards
    resolve theirs through the engine's ``module_registry``, kind
    ``kda_step``). -> ``(o [rows, h, dv] float32, pool)``."""
    del cfg
    step = step or STATE_STEPS[default_impl()]
    f32 = jnp.float32
    with scope("kda_step"):
        return step(pool, layer, slots, jnp.logical_not(fresh).astype(f32),
                    q.astype(f32), k.astype(f32), v.astype(f32),
                    g.astype(f32), beta.astype(f32))


# --------------------------------------------------------- the chunked form
def _inverse_of_unit_lower(a):
    """``(I + a)^-1`` for ``a`` [..., c, c] STRICTLY lower triangular: the
    product of ``I + (-a)^(2^j)`` over ``j < log2 c``, which is the whole
    Neumann series because ``a^c = 0``."""
    c = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    power = -a
    inv = jnp.eye(c, dtype=a.dtype) + power
    for _ in range(max(c - 1, 1).bit_length() - 1):
        power = mm(power, power)
        inv = inv + mm(inv, power)
    return inv


def _pair_products(x, k, run, sub):
    """``sum_c x_t[c] k_s[c] exp(run_t[c] - run_s[c])`` for ``s <= t`` (0
    above the diagonal), with no exponent that can overflow. HEAD-major, as
    everything inside a piece is: ``x`` [n, h, c, dk] (``n`` left sides at
    once: the keys for ``A``, the queries for the outputs), ``k`` and ``run``
    [h, c, dk] (``run`` the inclusive running sum of the log-decays,
    falling). -> [n, h, c, c]."""
    h, c, dk = k.shape
    nb = c // sub
    neg = -jnp.inf
    # the start of each sub-block: the running sum BEFORE its first row
    start = jnp.concatenate([jnp.zeros_like(run[:, :1]), run[:, :-1]],
                            axis=1)[:, ::sub]                 # [h, nb, dk]
    runb, kb = run.reshape(h, nb, sub, dk), k.reshape(h, nb, sub, dk)
    xb = x.reshape(-1, h, nb, sub, dk)
    # pairs of one sub-block: the difference itself, at most 0
    seen = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    near = jnp.exp(jnp.where(seen[:, :, None],
                             runb[:, :, :, None] - runb[:, :, None], neg))
    diag = jnp.sum(xb[:, :, :, :, None] * (kb[:, :, None] * near)[None],
                   axis=-1)                                # [n, h, nb, t, s]
    # ... on its place among the piece's columns
    place = jnp.eye(nb, dtype=diag.dtype)
    diag = jnp.einsum("nhbts,bd->nhbtds", diag, place).reshape(-1, h, c, c)
    if nb == 1:
        return diag
    # pairs across sub-blocks: both sides referred to the LATER one's start
    before = jnp.arange(c)[None, :] < (jnp.arange(nb) * sub)[:, None]
    far = k[:, None] * jnp.exp(jnp.where(
        before[:, :, None], start[:, :, None] - run[:, None], neg))
    left = xb * jnp.exp(runb - start[:, :, None])[None]
    off = jnp.einsum("nhbtc,hbsc->nhbts", left, far, precision=HIGHEST)
    return diag + off.reshape(-1, h, c, c)


def _piece(q, k, v, g, beta, state):
    """One piece of ONE sequence, ``c`` rows (a row that is not the piece's
    has ``k`` 0, ``g`` 0 and ``beta`` 0: it writes nothing and decays
    nothing), in float32 and HEAD-major throughout (a head's rows together:
    what every product here batches over): ``state`` [h, dk, dv] in and out.
    q, k, g [h, c, dk]; v [h, c, dv]; beta [h, c]."""
    c = q.shape[1]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    run = jnp.cumsum(g, axis=1)
    sub = c // 4 if c % 4 == 0 and c >= 4 else c
    kk, qk = _pair_products(jnp.stack([k, q]), k, run, sub)       # [h, c, c]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    inv = _inverse_of_unit_lower(
        jnp.where(strict, beta[:, :, None] * kk, 0.0))
    into = jnp.exp(run)                 # what the entering state decays by
    w = mm("hts,hsc->htc", inv, beta[:, :, None] * k * into)
    u = mm("hts,hsv->htv", inv, beta[:, :, None] * v)
    write = u - mm("htc,hcv->htv", w, state)                      # [h, c, dv]
    out = mm("htc,hcv->htv", q * into, state) \
        + mm("hts,hsv->htv", qk, write)
    left = jnp.exp(run[:, -1:] - run)       # a write's decay to the end
    new = jnp.exp(run[:, -1])[:, :, None] * state \
        + mm("hsc,hsv->hcv", k * left, write)
    return out, new


def chunked(q, k, v, g, beta, pool, layer, pieces, cfg):
    """The chunks of two tokens or more of a flat batch. ``q`` / ``k`` / ``g``
    [T, h, dk], ``v`` [T, h, dv], ``beta`` [T, h] as :func:`decode_step`
    takes them; ``pieces`` = ``(row0, length, slot, fresh)`` each [pieces],
    live ones first, and their count (``ragged.ssm_pieces``): rows ``row0 ..
    row0 + length`` of the flat axis are ``length <= kda_chunk_size``
    consecutive tokens of the sequence in state slot ``slot``, and ``fresh``
    says the first of them is the sequence's first. -> ``(o [T, h, dv]
    float32, zero where no piece lies; pool)``."""
    row0, length, slots, fresh, count = pieces
    c = cfg.kda_chunk_size
    t, f32 = q.shape[0], jnp.float32
    # head-major ONCE, outside the loop (a piece's window of [c, h, d] rows
    # turned head-major inside it was a sublane-wise copy a piece and
    # operand: 0.37 ms each on the v5e, PERF.md section 6, PR 55); and a
    # window of c rows from any row0 < T stays inside the padded arrays
    q, k, v, g, beta = (
        jnp.pad(jnp.swapaxes(a.astype(f32), 0, 1),
                ((0, 0), (0, c)) + ((0, 0),) * (a.ndim - 2))
        for a in (q, k, v, g, beta))
    window = lambda a, r0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        a, r0, c, axis=1)

    def piece(i, carry):
        pool, y_all = carry
        r0, slot = row0[i], slots[i]
        valid = jnp.arange(c) < length[i]
        rows = valid[:, None]
        # kda_chunk inside kda_scan: the pieces' own time, apart from the
        # state step of the one-token rows beside them (decode_step)
        with scope("kda_scan"), scope("kda_chunk"):
            state = jnp.where(fresh[i], 0.0, pool[layer, slot].astype(f32))
            y, state = _piece(
                window(q, r0), jnp.where(rows, window(k, r0), 0),
                window(v, r0), jnp.where(rows, window(g, r0), 0),
                jnp.where(valid, window(beta, r0), 0), state)
            pool = pool.at[layer, slot].set(state.astype(pool.dtype))
            y_all = jax.lax.dynamic_update_slice_in_dim(
                y_all, jnp.where(rows, y, window(y_all, r0)), r0, 1)
        return pool, y_all

    pool, y_all = jax.lax.fori_loop(
        0, count, piece,
        (pool, jnp.zeros((v.shape[0], t + c, v.shape[2]), f32)))
    return jnp.swapaxes(y_all[:, :t], 0, 1), pool
