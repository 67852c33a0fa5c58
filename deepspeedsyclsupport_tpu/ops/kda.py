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
its tail are Mamba-2's (``ops/ssm.conv_step`` for the one-token rows and
``ops/ssm.conv_pieces`` for the pieces: on the TPU the in-place kernels
``conv_tail_step`` and ``conv_pieces``), called with the layer's ``3 x h x
dk`` channels and no bias.

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
  ``kda_scan``. Two forms (:data:`PIECES`), chosen by the platform as the
  state step's are: on the TPU ONE Pallas kernel a layer over all the live
  pieces (``kda_piece``): q, k, g and v stay in HBM token-major as the
  mixer made them, a grid step copies each piece's window of ITS heads into
  VMEM (the next piece's while this one is computed) and reads a head's
  rows out of it with the heads' stride, the solve and the products run on
  two heads at a time in VMEM (:func:`_piece_heads`), the heads' states
  stay in VMEM while the slot stays (a 768-row chunk is twelve pieces of
  one slot) and go back to the pool, which is aliased to the output, when
  it changes (the next slot's come in behind the products), and a piece's
  live rows of ``y`` go out in copies of 2^k rows.
  Dead pieces cost nothing: the walk is a loop to the pieces' COUNT inside
  the kernel, and the grid is the head blocks alone. ``xla``: a
  ``fori_loop`` of :func:`_piece` over head-major copies of the operands
  (the CPU tests' form, and what the kernel is held against).
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


def _chunked_xla(q, k, v, g, beta, pool, layer, pieces, cfg):
    """:func:`chunked` as a loop of :func:`_piece` in XLA: the CPU's form,
    and what the kernel is held against."""
    row0, length, slots, fresh, count = pieces
    c = cfg.kda_chunk_size
    t, f32 = q.shape[0], jnp.float32
    # head-major ONCE, outside the loop (a piece's window of [c, h, d] rows
    # turned head-major inside it was a sublane-wise copy a piece and
    # operand: 0.37 ms each on the v5e, PERF.md section 6, PR 55; turned
    # here the compiler fuses the turn into what makes q, k, v and g); and a
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


# ------------------------------------------------------ the pieces' kernel
def _piece_heads(heads, live):
    """:func:`_piece` for the heads of ``heads`` = ``[(q, k, g, v, beta,
    state), ...]`` in two dimensions, as the kernel's body takes them (every
    product a plain matrix product, every mask an iota): q, k, g [c, dk], v
    [c, dv], beta [c, 1] float32, ``state`` [dk, dv]; ``live`` [c, 1] bool
    (a row that is not the piece's: k, g and beta to 0). -> ``[(y [c, dv],
    state), ...]``. The same sub-block rule: the pairs of one quarter piece
    a DIAGONAL at a time (row ``t`` against row ``t - d``: the rows rolled
    down by ``d``, ``exp(G_t - G_{t-d})`` itself, the product summed over
    the lanes), the pairs across quarters referred to the later one's
    start, all the later quarters in one product. A float32 product at
    ``HIGHEST`` costs the v5e about the same whatever its size up to the
    MXU's 128 x 128 (PERF.md section 6, PR 60), so there are as few as the
    mathematics allows: the running sum is rolls and adds, and the heads'
    ``[c, c]`` matrices go through the inverse, the writes and the read-out
    SIDE BY SIDE on the lanes against a block diagonal (two heads of 64
    rows fill the array once where each alone fills a quarter)."""
    f32 = jnp.float32
    n = len(heads)
    c, dk = heads[0][1].shape
    dv = heads[0][3].shape[1]
    sub = c // 4 if c % 4 == 0 and c >= 4 else c
    neg = -jnp.inf

    def mm(a, b, dims=((1,), (0,))):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                                   preferred_element_type=f32)

    def side(parts):
        return jnp.concatenate(parts, axis=1)

    def below(parts):
        return jnp.concatenate(parts, axis=0)

    def diagonal(p, width):
        """``[P_0 | P_1 ...]`` [r, n width] -> their block diagonal [n r, n
        width]."""
        at = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) // width
        return below([jnp.where(at == i, p, 0.0) for i in range(n)])

    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    qs, ks, gs, vs, betas, states = zip(*heads)
    ks = [jnp.where(live, k, 0.0) for k in ks]
    betas = [jnp.where(live, beta, 0.0) for beta in betas]
    # the inclusive running sums: log2(c) rolls down the sublanes and adds
    # (a product with a triangle of ones is six loads of the MXU's weights)
    runs = [jnp.where(live, g, 0.0) for g in gs]
    step = 1
    while step < c:
        runs = [run + jnp.where(t >= step, pltpu.roll(run, step, 0), 0.0)
                for run in runs]
        step *= 2
    powers, qks = [], []
    for q, k, beta, run in zip(qs, ks, betas, runs):
        # unrolled: a roll by a STATIC count is a few sublane shuffles, one
        # by a traced count cost 2.1 of a head's 5.0 us on the v5e (PERF.md
        # section 6, PR 60)
        kk = qk = jnp.zeros((c, c), f32)
        for d in range(sub):
            far = jnp.exp(jnp.where(t % sub >= d,
                                    run - pltpu.roll(run, d, 0), neg)) \
                * pltpu.roll(k, d, 0)
            at = col == row - d
            kk = jnp.where(at, jnp.sum(k * far, axis=1, keepdims=True), kk)
            qk = jnp.where(at, jnp.sum(q * far, axis=1, keepdims=True), qk)
        if sub < c:
            # quarter b's [k; q] rows, one quarter below the other, against
            # the rows BEFORE each (their far sides below one another: 16 +
            # 32 + 48 of a piece's 64, one tile of the MXU's weights); of
            # the product the blocks on its diagonal, each rolled to lane 0
            near, far, cut = [], [], [0]
            for b in range(1, c // sub):
                own = slice(b * sub, (b + 1) * sub)
                start = run[b * sub - 1:b * sub]
                grow = jnp.exp(run[own] - start)
                near += [k[own] * grow, q[own] * grow]
                far.append(k[:b * sub] * jnp.exp(start - run[:b * sub]))
                cut.append(cut[-1] + b * sub)
            far.append(jnp.zeros((2 * c - cut[-1], dk), f32))
            off = mm(below(near), below(far), ((1,), (1,)))   # [.., 2 c]
            lane = jax.lax.broadcasted_iota(jnp.int32, (2 * sub, 2 * c), 1)
            off = [jnp.where(lane < (b + 1) * sub,
                             pltpu.roll(off[2 * b * sub:2 * (b + 1) * sub],
                                        (2 * c - cut[b]) % (2 * c), 1),
                             0.0)[:, :c]
                   for b in range(c // sub - 1)]
            first = [jnp.zeros((sub, c), f32)]
            kk = kk + below(first + [o[:sub] for o in off])
            qk = qk + below(first + [o[sub:] for o in off])
        powers.append(-jnp.where(row > col, beta * kk, 0.0))
        qks.append(qk)
    # (I + A)^-1, A strictly lower: the Neumann series, doubled. The inverse
    # so far times the power and the power's own square are ONE product,
    # the one on top of the other ([inv; P] P = [inv P; P^2])
    power = side(powers)
    inv = side([(row == col).astype(f32)] * n) + power
    doublings = max(c - 1, 1).bit_length() - 1
    if doublings:
        power = mm(power, diagonal(power, c))
    for j in range(doublings):
        if j + 1 == doublings:
            inv = inv + mm(inv, diagonal(power, c))
        else:
            both = mm(below([inv, power]), diagonal(power, c))
            inv, power = inv + both[:c], both[c:]
    # what the entering state holds for the keys and the queries needs no
    # inverse, and the writes are T (beta V - (beta K e^G) S) in one product
    intos = [jnp.exp(run) for run in runs]
    held = [mm(below([beta * k * into, q * into]), state)
            for q, k, beta, into, state in zip(qs, ks, betas, intos, states)]
    writes = mm(diagonal(inv, c), below([beta * v - h[:c] for v, beta, h
                                         in zip(vs, betas, held)]))
    writes = [writes[i * c:(i + 1) * c] for i in range(n)]
    read = mm(diagonal(side(qks), c), below(writes))
    lasts = [run[c - 1:c] for run in runs]
    wrote = mm(below([k * jnp.exp(last - run)
                      for k, last, run in zip(ks, lasts, runs)]),
               diagonal(side(writes), dv), ((0,), (0,)))      # [dk, n dv]
    eye = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    out = []
    for i, (h, last, state) in enumerate(zip(held, lasts, states)):
        # exp(G_last) down the sublanes, as the state's key channels lie
        decay = jnp.sum(jnp.where(eye, jnp.broadcast_to(jnp.exp(last),
                                                        (dk, dk)), 0.0),
                        axis=1, keepdims=True)
        out.append((h[c:] + read[i * c:(i + 1) * c],
                    decay * state + wrote[:, i * dv:(i + 1) * dv]))
    return out


# heads a grid step of the pieces' kernel takes, in whole sublane tiles of
# the rows' [heads, d] minor dimensions (HEAD_TILE): 8, 16, 32 and 64 read
# the same on the v5e (PERF.md section 6, PR 60), and 16 holds 7 MiB of VMEM.
# PACK_LANES: the lanes the heads' [c, c] matrices may fill side by side
PIECE_HEADS = 16
HEAD_TILE = 8
PACK_LANES = 128


def _piece_kernel(layer_ref, row0_ref, length_ref, slot_ref, fresh_ref,
                  count_ref, beta_ref, q_hbm, k_hbm, g_hbm, v_hbm, zero_hbm,
                  pool_hbm, y_hbm, out_hbm, win, win_v, rows, rows_v, y_rows,
                  y_win, state, rsem, ssem, ysem, *, chunk, pack):
    """Grid step ``b``: ``heads`` heads of EVERY live piece, one after
    another. A piece's window of ``chunk`` rows comes token-major, as the
    mixer left it, into one of two buffers (the next piece's while this one
    is computed); a head's rows are read out of it with the heads' stride
    (the head-major turn); the heads' states stay in ``state`` while the
    slot stays and go back to the pool when it changes or the pieces end; a
    piece's LIVE rows go out in copies of 2^k rows, the bits of its length."""
    # y's zeros and the pool are aliased to the outputs, and a slot is read
    # where its last piece wrote it: through the output's ref
    del zero_hbm, pool_hbm
    f32 = jnp.float32
    c = chunk
    t_rows = q_hbm.shape[0]
    groups, tile = win.shape[3:5]
    heads = groups * tile
    b = pl.program_id(0)
    layer, count = layer_ref[0], count_ref[0]

    def start_of(i):
        """The window's first row: ``row0``, or ``T - c`` where the window
        would pass the end (the piece's rows then stand ``off`` rows in)."""
        return jnp.minimum(row0_ref[i], t_rows - c)

    def row_copies(i, par):
        at = (pl.ds(start_of(i), c), pl.ds(b * groups, groups))
        return [pltpu.make_async_copy(src.at[at], dst, rsem.at[par, n])
                for n, (src, dst) in enumerate(
                    [(q_hbm, win.at[par, 0]), (k_hbm, win.at[par, 1]),
                     (g_hbm, win.at[par, 2]), (v_hbm, win_v.at[par])])]

    def state_copy(i, buf, out):
        """Piece ``i``'s slot and buffer ``buf`` of the two: out to the pool,
        or in from it."""
        at = (layer, slot_ref[i], pl.ds(b * heads, heads))
        return pltpu.make_async_copy(state.at[buf], out_hbm.at[at],
                                     ssem.at[1, buf]) \
            if out else pltpu.make_async_copy(out_hbm.at[at], state.at[buf],
                                              ssem.at[0, buf])

    def moved(i):
        """Piece ``i`` has a predecessor, in another slot."""
        return (i > 0) & (slot_ref[i] != slot_ref[jnp.maximum(i - 1, 0)])

    def comes_in(i):
        """Piece ``i`` takes its state from the pool: no predecessor left it
        here, and it is not its sequence's first."""
        return ((i == 0) | moved(i)) & (fresh_ref[i] == 0)

    def y_copies(i, par):
        """``(bit set, copy)`` for each power of two up to ``chunk``: the
        rows ``off .. off + length`` of the window, the largest run first."""
        off = row0_ref[i] - start_of(i)
        n = length_ref[i]
        out = []
        for bit in reversed(range(c.bit_length())):
            size = 1 << bit
            first = off + ((n >> (bit + 1)) << (bit + 1))
            first = jnp.minimum(first, c - size)       # unset bits: in range
            out.append((((n >> bit) & 1) == 1, pltpu.make_async_copy(
                y_win.at[par, pl.ds(first, size)],
                y_hbm.at[pl.ds(start_of(i) + first, size),
                         pl.ds(b * groups, groups)], ysem.at[par, bit])))
        return out

    def y_wait(i, par):
        for on, copy in y_copies(i, par):
            pl.when(on)(copy.wait)

    @pl.when(count > 0)
    def _():
        for copy in row_copies(0, 0):
            copy.start()

        @pl.when(comes_in(0))
        def _():
            state_copy(0, 0, False).start()

    def piece(i, buf):
        """Piece ``i`` against the state in buffer ``buf``; -> the buffer
        the next piece's state is in."""
        par = jax.lax.rem(i, 2)
        for copy in row_copies(i, par):
            copy.wait()

        @pl.when(i + 1 < count)
        def _():
            for copy in row_copies(i + 1, 1 - par):
                copy.start()

        # the state: its slot's predecessor left it in this buffer, or it
        # was sent for a piece ago (piece 0's before the loop), or zeros
        pl.when(comes_in(i))(state_copy(i, buf, False).wait)

        @pl.when(fresh_ref[i] != 0)
        def _():
            state[buf] = jnp.zeros(state.shape[1:], f32)

        # the turn: a head's rows out of the token-major window
        def turn(gi, _):
            for j in range(tile):
                for n in range(3):
                    rows[n, gi * tile + j] = win[par, n, :, gi, j, :]
                rows_v[gi * tile + j] = win_v[par, :, gi, j, :]
            return 0

        jax.lax.fori_loop(0, groups, turn, 0)
        # the predecessor's slot went out of the OTHER buffer when its last
        # piece was done: once it is out (every copy out waited for before a
        # copy in starts), the next slot's state comes into that buffer
        # behind this piece's products
        after = jnp.minimum(i + 1, count - 1)
        leaves = (i + 1 == count) | (slot_ref[after] != slot_ref[i])
        pl.when(moved(i))(state_copy(i - 1, 1 - buf, True).wait)

        @pl.when((i + 1 < count) & comes_in(after))
        def _():
            state_copy(after, 1 - buf, False).start()

        off = row0_ref[i] - start_of(i)
        row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        live = (row >= off) & (row < off + length_ref[i])
        betas = beta_ref[pl.ds(start_of(i), c), :]             # [c, h]
        lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)

        def some(p, _):
            at = [p * pack + i for i in range(pack)]
            done = _piece_heads(
                [(rows[0, j], rows[1, j], rows[2, j], rows_v[j],
                  jnp.sum(jnp.where(lane == b * heads + j, betas, 0.0),
                          axis=1, keepdims=True), state[buf, j])
                 for j in at], live)
            for j, (y, new) in zip(at, done):
                y_rows[j] = y
                state[buf, j] = new
            return 0

        jax.lax.fori_loop(0, heads // pack, some, 0)

        # y's buffer of this parity last went out two pieces ago
        @pl.when(i >= 2)
        def _():
            y_wait(i - 2, par)

        def back(gi, _):
            for j in range(tile):
                y_win[par, :, gi, j, :] = y_rows[gi * tile + j]
            return 0

        jax.lax.fori_loop(0, groups, back, 0)
        for on, copy in y_copies(i, par):
            pl.when(on)(copy.start)
        # ... and the state goes back when its slot's last piece is done
        pl.when(leaves)(state_copy(i, buf, True).start)
        return jnp.where(leaves, 1 - buf, buf)

    last = jax.lax.fori_loop(0, count, piece, jnp.int32(0))

    @pl.when(count >= 1)
    def _():
        state_copy(count - 1, 1 - last, True).wait()
        y_wait(count - 1, jax.lax.rem(count - 1, 2))

    @pl.when(count >= 2)
    def _():
        y_wait(count - 2, jax.lax.rem(count, 2))


def _chunked_pallas(q, k, v, g, beta, pool, layer, pieces, cfg,
                    interpret=False, heads=None, pack=None):
    """:func:`chunked` as ONE ``pallas_call`` over all the live pieces
    (``kda_piece``): q, k, g, v stay in HBM token-major as they came, the
    pool is aliased to the output, ``y`` is written over zeros."""
    row0, length, slots, fresh, count = pieces
    c = cfg.kda_chunk_size
    t, h, dk = q.shape
    dv = v.shape[-1]
    f32, i32 = jnp.float32, jnp.int32
    rows_in = (q, k, g, v, beta)
    if t < c:       # a window is c rows: a batch shorter than one is padded
        rows_in = [jnp.pad(a, ((0, c - t),) + ((0, 0),) * (a.ndim - 1))
                   for a in rows_in]
    tile = _divisor(h, HEAD_TILE)
    hb = heads or tile * _divisor(h // tile, max(PIECE_HEADS // tile, 1))
    groups = hb // tile
    # heads whose [c, c] matrices go through the inverse side by side
    pack = pack or _divisor(hb, max(PACK_LANES // c, 1))
    # [T, h, d] as [T, h / tile, tile, d]: the same bytes (a head tile is a
    # sublane tile), and a group of heads is a MAJOR index in the kernel
    q, k, g, v = (a.astype(f32).reshape(a.shape[0], h // tile, tile, -1)
                  for a in rows_in[:4])
    beta = rows_in[4].astype(f32)
    tp = q.shape[0]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(h // hb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] + [in_hbm] * 6,
        out_specs=[in_hbm, in_hbm],
        scratch_shapes=[
            pltpu.VMEM((2, 3, c, groups, tile, dk), f32),
            pltpu.VMEM((2, c, groups, tile, dv), f32),
            pltpu.VMEM((3, hb, c, dk), f32),
            pltpu.VMEM((hb, c, dv), f32),
            pltpu.VMEM((hb, c, dv), f32),
            pltpu.VMEM((2, c, groups, tile, dv), f32),
            pltpu.VMEM((2, hb, dk, dv), pool.dtype),
            pltpu.SemaphoreType.DMA((2, 4)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, c.bit_length()))])
    # two windows of four operands, their head-major rows, y both ways
    held = 4 * (2 * 4 + 4 + 1 + 2) * c * hb * max(dk, dv) \
        + 2 * hb * dk * dv * pool.dtype.itemsize + beta.size * 4
    y, pool = pl.pallas_call(
        functools.partial(_piece_kernel, chunk=c, pack=pack),
        out_shape=[jax.ShapeDtypeStruct((tp, h // tile, tile, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch six: y's zeros are the 12th,
        # the pool the 13th
        input_output_aliases={11: 0, 12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(max(2 * held + (16 << 20), 32 << 20),
                                 100 << 20)),
        interpret=interpret, name="kda_piece",
    )(jnp.asarray(layer, i32).reshape(1), row0.astype(i32),
      length.astype(i32), slots.astype(i32), fresh.astype(i32),
      jnp.asarray(count, i32).reshape(1), beta, q, k, g, v,
      jnp.zeros((tp, h // tile, tile, dv), f32), pool)
    return y.reshape(tp, h, dv)[:t], pool


PIECES = {
    "xla": _chunked_xla,
    "pallas": _chunked_pallas,
    "pallas_interpret": functools.partial(_chunked_pallas, interpret=True),
}


def chunked(q, k, v, g, beta, pool, layer, pieces, cfg, form=None):
    """The chunks of two tokens or more of a flat batch. ``q`` / ``k`` / ``g``
    [T, h, dk], ``v`` [T, h, dv], ``beta`` [T, h] as :func:`decode_step`
    takes them; ``pieces`` = ``(row0, length, slot, fresh)`` each [pieces],
    live ones first, and their count (``ragged.ssm_pieces``): rows ``row0 ..
    row0 + length`` of the flat axis are ``length <= kda_chunk_size``
    consecutive tokens of the sequence in state slot ``slot``, and ``fresh``
    says the first of them is the sequence's first. ``form``: one of
    :data:`PIECES` (None: by platform; the serving forwards resolve theirs
    through the engine's ``module_registry``, kind ``kda_chunk``). -> ``(o
    [T, h, dv] float32, zero where no piece lies; pool)``."""
    form = form or PIECES[default_impl()]
    # kda_chunk inside kda_scan: the pieces' own time, apart from the state
    # step of the one-token rows beside them (decode_step)
    with scope("kda_scan"), scope("kda_chunk"):
        return form(q, k, v, g, beta, pool, layer, pieces, cfg)
