"""Power retention of degree 2 (arXiv:2507.04239) for the serving path: the
recurrence of one layer over a continuous batch, against a state that lives
per sequence SLOT and never grows with the context.

``h`` query heads of ``d``, ``hk`` key-value heads (query head ``i`` reads
head ``i // (h / hk)``), a gate a KV head. Per token, with ``q`` and ``k``
as normed and rotated, ``s`` the logit scale and ``gam = log sigmoid(gate)``:

    S_t = exp(gam) S_{t-1} + phi(k_t) v_t^T          z_t likewise, + phi(k_t)
    y_t = phi(s q_t)^T S_t / (phi(s q_t)^T z_t + eps)

``phi(a) . phi(b) = (a . b)^2``: the symmetric square of a ``d``-wide vector.
Its LAYOUT here is by diagonals of the ``d x d`` square, which is what makes
it cheap to expand on the lanes: row ``o`` of :func:`phi` holds ``a_m x
a_{(m + o) mod d}`` for every ``m``, ``o = 0 .. d / 2``: a lane rotation and
a product. Diagonal ``o`` and diagonal ``d - o`` of the square are the same
products, so rows ``1 .. d / 2 - 1`` carry the weight sqrt(2) (on each side)
and rows ``0`` and ``d / 2`` the weight 1 (row ``d / 2`` holds each of its
pairs twice). ``STATE_DIM = (d / 2 + 1) x d``: 8,320 at ``d`` 128, against
the 8,256 distinct products and the 16,384 of the whole square.

The state pool (``inference/v2/kv_cache.BlockedKV.ret_s`` / ``.ret_z``)
holds, per layer and slot, ``S`` as ``[hk, d, STATE_DIM]`` float32 (the
VALUE's ``d`` on the sublanes, the features on the lanes: the update is then
a sublane-broadcast row times a column, the read-out a product and a sum
over lanes, all on whole registers) and ``z`` as ``[hk, STATE_DIM]``. Slot
``S`` (the last) is the sink padding writes to. A piece whose first position
is 0 starts from zeros, whatever its slot held: the host resets nothing.

Two entries, as ``ops/ssm.py`` has:

* :func:`decode_step` — ONE token for each of ``[rows]`` slots, the state
  updated IN PLACE. On the TPU a Pallas kernel whose block is a tile of the
  pool's own ``[layer, slot, kv head]`` (scalar-prefetch indices, the pool
  aliased to the output): each state is read once and written once, and the
  group's ``h / hk`` queries are read out of the same pass. ``xla``: gather,
  update, scatter (the CPU tests' reference, and what the kernel is held
  against). ``z`` is 1/128 of the bytes and stays in XLA for both.
* :func:`chunked` — the pieces of the chunks of two tokens or more in one
  flat batch (``ragged.ssm_pieces``): inside a piece the masked quadratic
  form ``(s q . k)^2`` under the gates' decay (XLA products over ``[rows,
  rows]``), the earlier pieces through the slot's ``S`` and ``z``: on the
  TPU a second Pallas kernel (:func:`_carry_kernel`) that reads the state
  out for the piece's rows and leaves the piece's own write behind, tile by
  tile of the pool in place, and EXPANDS THE FEATURES INSIDE IT, a lane
  rotation and a product a diagonal: ``[rows, 8320]`` a head never goes to
  HBM (as XLA products it did, twice, and took ten times as long: PERF.md
  section 6, PR 49). Its pieces carry the scope ``ret_chunk`` inside
  ``ret_scan``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor.mfu import scope
from .ssm import default_impl

HIGHEST = jax.lax.Precision.HIGHEST
# the normaliser's floor: y = phi(q)^T S / (phi(q)^T z + eps)
NORMALISER_EPS = 1e-6


def state_dim(head_dim: int) -> int:
    """Features of :func:`phi` over a ``head_dim``-wide vector."""
    return (head_dim // 2 + 1) * head_dim


def phi_weights(head_dim: int) -> np.ndarray:
    """``[d / 2 + 1, 1]``: what each row of :func:`phi` is multiplied by."""
    w = np.full((head_dim // 2 + 1, 1), np.sqrt(2.0), np.float32)
    w[0] = w[-1] = 1.0
    return w


def phi(a):
    """``a`` [..., d] float32 -> [..., STATE_DIM]: ``phi(a) . phi(b) = (a .
    b)^2``, by diagonals (row ``o``: ``a_m a_{(m + o) mod d}``)."""
    d = a.shape[-1]
    wrapped = jnp.concatenate([a, a[..., :d // 2]], axis=-1)
    rolled = jnp.stack([wrapped[..., o:o + d] for o in range(d // 2 + 1)],
                       axis=-2)
    return (a[..., None, :] * rolled * phi_weights(d)).reshape(
        *a.shape[:-1], -1)


def _normalised(num, den):
    """``num`` [..., d] over the state's own normaliser ``den`` [...]."""
    return num / (den + NORMALISER_EPS)[..., None]


def _scale(cfg) -> float:
    return cfg.attn_scale if cfg.attn_scale is not None \
        else cfg.head_dim ** -0.5


# ----------------------------------------------------------- the decode step
def _state_step_xla(pool, layer, slots, decay, fq, fk, v):
    """``pool[layer, slots]`` one step on: gather, update, scatter (a
    scatter on the loop-carried pool is in place; the gather is a copy).
    ``decay`` [rows, hk] float32: 0 starts the row from zeros. ``fq`` [rows,
    hk, g, D], ``fk`` [rows, hk, D], ``v`` [rows, hk, d] float32. ->
    ``(phi(q)^T S [rows, hk, g, d], pool)``."""
    state = pool[layer, slots] * decay[:, :, None, None]
    new = state + v[:, :, :, None] * fk[:, :, None, :]
    y = jnp.einsum("rjvf,rjgf->rjgv", new, fq, precision=HIGHEST)
    return y, pool.at[layer, slots].set(new.astype(pool.dtype))


def _state_step_kernel(layer_ref, slots_ref, feat_ref, v_ref, st_ref, y_ref,
                       out_ref, *, group):
    """One tile ``[d, Dt]`` of one KV head's state: read once, written once,
    the group's queries read out of what was written. ``feat`` [8k, Dt]:
    rows ``0 .. group - 1`` the queries' features, row ``group`` the key's,
    row ``group + 1`` the decay on every lane."""
    del layer_ref, slots_ref          # the BlockSpecs' own
    feat = feat_ref[...]
    new = st_ref[...].astype(jnp.float32) * feat[group + 1:group + 2, :] \
        + v_ref[...] * feat[group:group + 1, :]
    out_ref[...] = new.astype(out_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
    part = jnp.zeros(y_ref.shape, jnp.float32)
    for i in range(group):
        col = jnp.sum(new * feat[i:i + 1, :], axis=1, keepdims=True)
        part = jnp.where(lane == i, col, part)

    @pl.when(pl.program_id(2) == 0)
    def _():
        y_ref[...] = part

    @pl.when(pl.program_id(2) > 0)
    def _():
        y_ref[...] += part


# features a grid step of the kernel covers: [128, 1664] float32 is 0.85 MB,
# in and out double-buffered 3.4 MB
STEP_FEATURES = 1664
Y_LANES = 128       # the read-out's block: query i in lane i


def _feature_tile(dim: int) -> int:
    """The largest tile of whole 128 lanes that divides ``dim`` and is at
    most :data:`STEP_FEATURES`; ``dim`` itself where 128 does not divide it
    (a test's width: the block is then the whole axis)."""
    if dim % 128:
        return dim
    n = dim // 128
    return 128 * max(t for t in range(1, STEP_FEATURES // 128 + 1)
                     if n % t == 0)


def _state_step_pallas(pool, layer, slots, decay, fq, fk, v, interpret=False):
    """The same step with the pool aliased to the output: tile ``[layer,
    slots[row], head, :, tile]`` of the pool in, the same tile out, the
    read-out summed over a head's tiles. Rows that share a slot (the sink)
    write it one after another; nobody reads it."""
    rows, hk, d, dim = (slots.shape[0], *pool.shape[2:])
    g = fq.shape[2]
    assert g <= Y_LANES, g
    pad = -(g + 2) % 8
    feat = jnp.concatenate(
        [fq, fk[:, :, None], jnp.broadcast_to(decay[:, :, None, None],
                                              (rows, hk, 1, dim)),
         jnp.zeros((rows, hk, pad, dim), jnp.float32)], axis=2)
    tile = _feature_tile(dim)
    small = lambda r, j, t, *_: (r, j, 0, 0)              # noqa: E731
    state = lambda r, j, t, layer_ref, slots_ref: (       # noqa: E731
        layer_ref[0], slots_ref[r], j, 0, t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(rows, hk, dim // tile),
        in_specs=[pl.BlockSpec((None, None, g + 2 + pad, tile),
                               lambda r, j, t, *_: (r, j, 0, t)),
                  pl.BlockSpec((None, None, d, 1), small),
                  pl.BlockSpec((None, None, None, d, tile), state)],
        out_specs=[pl.BlockSpec((None, None, d, Y_LANES), small),
                   pl.BlockSpec((None, None, None, d, tile), state)])
    y, pool = pl.pallas_call(
        functools.partial(_state_step_kernel, group=g),
        out_shape=[jax.ShapeDtypeStruct((rows, hk, d, Y_LANES), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch two: the pool is the 5th
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name="ret_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      feat, v[..., None], pool)
    return y[..., :g].swapaxes(2, 3), pool


STATE_STEPS = {
    "xla": _state_step_xla,
    "pallas": _state_step_pallas,
    "pallas_interpret": functools.partial(_state_step_pallas,
                                          interpret=True),
}


def decode_step(q, k, v, gam, pools, layer, slots, fresh, cfg, step=None):
    """One token for each row. ``q`` [rows, h, d], ``k`` / ``v`` [rows, hk,
    d] as the projections, norms and rotary give them; ``gam`` [rows, hk]
    float32, the log of the gate; ``pools`` = ``(S, z)``, ``layer`` the
    layer, ``slots`` [rows] each row's state slot (the sink for a row that
    is padding), ``fresh`` [rows] bool: the row is its sequence's first
    token. ``step``: one of :data:`STATE_STEPS` (None: by platform; the
    serving forwards resolve theirs through the engine's
    ``module_registry``, kind ``ret_step``). -> ``(y [rows, h, d] float32,
    (S, z))``."""
    step = step or STATE_STEPS[default_impl()]
    s_pool, z_pool = pools
    rows, h, d = q.shape
    hk = k.shape[1]
    f32 = jnp.float32
    decay = jnp.where(fresh[:, None], 0.0, jnp.exp(gam))
    fq = phi(q.astype(f32).reshape(rows, hk, h // hk, d) * _scale(cfg))
    fk = phi(k.astype(f32))
    z = z_pool[layer, slots] * decay[:, :, None] + fk
    z_pool = z_pool.at[layer, slots].set(z.astype(z_pool.dtype))
    den = jnp.einsum("rjf,rjgf->rjg", z, fq, precision=HIGHEST)
    num, s_pool = step(s_pool, layer, slots, decay, fq, fk, v.astype(f32))
    y = _normalised(num, den)
    return y.reshape(rows, h, d), (s_pool, z_pool)


# --------------------------------------------------------- the chunked form
def _carry_xla(pools, layer, slot, qs, k, v, left, last, dtype):
    """What a piece reads of the state it enters with and what it leaves
    there, gather / compute / scatter: ``qs`` [c, hk, g, d] (scaled), ``k``
    / ``v`` [c, hk, d] float32, ``left`` [c, hk] what row u's write has
    decayed to at the piece's end, ``last`` [hk] the whole piece's decay (0:
    the piece starts from zeros). -> ``(phi(q)^T S [c, hk, g, d], phi(q)^T z
    [c, hk, g], pools)``, the read-out of the state AS IT WAS."""
    s_pool, z_pool = pools
    f32 = jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32,
                           precision=HIGHEST if dtype == f32 else None)
    state, norm = s_pool[layer, slot], z_pool[layer, slot]
    fq = phi(qs).astype(dtype)
    num = mm("tjgf,jvf->tjgv", fq, state.astype(dtype))
    den = mm("tjgf,jf->tjg", fq, norm.astype(dtype))
    fk = phi(k) * left[:, :, None]
    state = last[:, None, None] * state.astype(f32) + mm(
        "ujf,ujv->jvf", fk.astype(dtype), v.astype(dtype))
    norm = last[:, None] * norm.astype(f32) + fk.sum(0)
    return num, den, (s_pool.at[layer, slot].set(state.astype(s_pool.dtype)),
                      z_pool.at[layer, slot].set(norm.astype(z_pool.dtype)))


# diagonals of the feature map a grid step of the piece's kernel covers
STEP_DIAGONALS = 13


def _carry_kernel(layer_ref, slot_ref, qs_ref, k_ref, vt_ref, left_ref,
                  last_ref, st_ref, z_ref, num_ref, den_ref, out_ref,
                  zout_ref, *, steps, dtype):
    """``steps`` diagonals of one KV head's state: each a ``[d, d]`` tile,
    read once and written once. The features are EXPANDED HERE, a lane
    rotation and a product a diagonal: ``[rows, features]`` never exists."""
    del layer_ref, slot_ref           # the BlockSpecs' own
    f32 = jnp.float32
    d = qs_ref.shape[-1]
    qs, k = qs_ref[...], k_ref[...]
    kl = k * left_ref[...]            # [c, d] x [c, 1]
    last = last_ref[...]              # [1, d], the piece's decay on each lane
    num = jnp.zeros(num_ref.shape, f32)
    den = jnp.zeros(den_ref.shape, f32)
    contract_lanes = (((1,), (1,)), ((), ()))
    for i in range(steps):
        o = pl.program_id(1) * steps + i
        shift = jax.lax.rem(d - o, d)
        w = jnp.where((o == 0) | (o == d // 2), 1.0, np.sqrt(2.0)
                      ).astype(f32)
        fq = qs * pltpu.roll(qs, shift, 1) * w
        # (the key's weight once: kl carries ``left``, its rotation does not)
        fk = kl * pltpu.roll(k, shift, 1) * w
        tile = slice(i * d, (i + 1) * d)
        st, zo = st_ref[:, tile].astype(f32), z_ref[:, tile].astype(f32)
        num = num + jax.lax.dot_general(
            fq.astype(dtype), st.astype(dtype), contract_lanes,
            preferred_element_type=f32)
        den = den + fq * zo
        out_ref[:, tile] = (st * last + jnp.dot(
            vt_ref[...], fk.astype(dtype), preferred_element_type=f32)
        ).astype(out_ref.dtype)
        zout_ref[:, tile] = (zo * last + jnp.sum(fk, axis=0, keepdims=True)
                             ).astype(zout_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        num_ref[...] = num
        den_ref[...] = den

    @pl.when(pl.program_id(1) > 0)
    def _():
        num_ref[...] += num
        den_ref[...] += den


def _carry_pallas(pools, layer, slot, qs, k, v, left, last, dtype,
                  interpret=False):
    """:func:`_carry_xla` with the pools aliased to the outputs: per KV head
    and :data:`STEP_DIAGONALS` diagonals, the tile ``[layer, slot, head, :,
    diagonals]`` of ``S`` in and out, the group's queries stacked on the
    rows. ``z`` rides as ``[.., 1, D]``."""
    s_pool, z_pool = pools
    c, hk, g, d = qs.shape
    dim = s_pool.shape[-1]
    n_diag = dim // d
    steps = max(t for t in range(1, STEP_DIAGONALS + 1) if n_diag % t == 0)
    if (steps * d) % 128 and steps != n_diag:
        steps = n_diag                # a test's width: the whole axis a step
    wide = steps * d
    z5 = z_pool.reshape(*z_pool.shape[:3], 1, dim)
    head = lambda j, t, *_: (j, 0, 0)                        # noqa: E731
    state = lambda j, t, layer_ref, slot_ref: (              # noqa: E731
        layer_ref[0], slot_ref[0], j, 0, t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(hk, n_diag // steps),
        in_specs=[pl.BlockSpec((None, c * g, d), head),
                  pl.BlockSpec((None, c, d), head),
                  pl.BlockSpec((None, d, c), head),
                  pl.BlockSpec((None, c, 1), head),
                  pl.BlockSpec((None, 1, d), head),
                  pl.BlockSpec((None, None, None, d, wide), state),
                  pl.BlockSpec((None, None, None, 1, wide), state)],
        out_specs=[pl.BlockSpec((None, c * g, d), head),
                   pl.BlockSpec((None, c * g, d), head),
                   pl.BlockSpec((None, None, None, d, wide), state),
                   pl.BlockSpec((None, None, None, 1, wide), state)])
    f32 = jnp.float32
    num, den, s_pool, z5 = pl.pallas_call(
        functools.partial(_carry_kernel, steps=steps, dtype=dtype),
        out_shape=[jax.ShapeDtypeStruct((hk, c * g, d), f32),
                   jax.ShapeDtypeStruct((hk, c * g, d), f32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype),
                   jax.ShapeDtypeStruct(z5.shape, z5.dtype)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch two: the pools are 7th and 8th
        input_output_aliases={7: 2, 8: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name="ret_piece",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(slot, jnp.int32).reshape(1),
      # a head's rows: the piece's rows, the group's queries within each
      qs.transpose(1, 0, 2, 3).reshape(hk, c * g, d),
      k.transpose(1, 0, 2), v.transpose(1, 2, 0).astype(dtype),
      left.T[:, :, None], jnp.broadcast_to(last[:, None, None], (hk, 1, d)),
      s_pool, z5)
    num = num.reshape(hk, c, g, d).transpose(1, 0, 2, 3)
    den = den.sum(-1).reshape(hk, c, g).transpose(1, 0, 2)
    return num, den, (s_pool, z5.reshape(z_pool.shape))


PIECE_CARRIES = {
    "xla": _carry_xla,
    "pallas": _carry_pallas,
    "pallas_interpret": functools.partial(_carry_pallas, interpret=True),
}


def _piece(q, k, v, gam, keep, pools, layer, slot, cfg, dtype, carry):
    """One piece of ONE sequence, ``c`` rows (a row that is not the piece's
    has ``k`` 0 and ``gam`` 0: it adds nothing and decays nothing): the
    masked quadratic form inside the piece, the state of ``pools[layer,
    slot]`` read and left behind through ``carry`` (``keep`` false: the
    piece starts from zeros, whatever the slot holds). q [c, h, d]; k, v [c,
    hk, d]; gam [c, hk] float32. The products take their operands in
    ``dtype`` (the activations') and accumulate in float32."""
    c, h, d = q.shape
    hk = k.shape[1]
    f32 = jnp.float32
    mm = functools.partial(jnp.einsum, preferred_element_type=f32,
                           precision=HIGHEST if dtype == f32 else None)
    qs = q.astype(f32).reshape(c, hk, h // hk, d) * _scale(cfg)
    cum = jnp.cumsum(gam, axis=0)                              # [c, hk]
    seen = jnp.tril(jnp.ones((c, c), bool))
    # exp(cum_t - cum_u) for u <= t: what row u's write has decayed to at t
    lmat = jnp.exp(jnp.where(seen[:, :, None],
                             cum[:, None, :] - cum[None, :, :], -jnp.inf))
    sc = mm("tjgd,ujd->jgtu", qs.astype(dtype), k.astype(dtype))
    a = sc * sc * lmat.transpose(2, 0, 1)[:, None]            # [hk,g,c,c]
    num = mm("jgtu,ujd->tjgd", a.astype(dtype), v.astype(dtype))
    den = a.sum(-1).transpose(2, 0, 1)                         # [c, hk, g]
    # the state the piece entered with, decayed to each row; and what it
    # leaves: everything decayed to the last row
    into = jnp.exp(cum) * keep
    s_num, s_den, pools = carry(
        pools, layer, slot, qs, k.astype(f32), v.astype(f32),
        jnp.exp(cum[-1][None] - cum), jnp.exp(cum[-1]) * keep, dtype)
    num = num + into[:, :, None, None] * s_num
    den = den + into[:, :, None] * s_den
    return _normalised(num, den).reshape(c, h, d), pools


def chunked(q, k, v, gam, pools, layer, pieces, cfg, carry=None):
    """The chunks of two tokens or more of a flat batch. ``q`` [T, h, d],
    ``k`` / ``v`` [T, hk, d], ``gam`` [T, hk]; ``pieces`` = ``(row0, length,
    slot, fresh)`` each [pieces], live ones first, and their count
    (``ragged.ssm_pieces``): rows ``row0 .. row0 + length`` of the flat axis
    are ``length <= retention_chunk_size`` consecutive tokens of the
    sequence in state slot ``slot``, and ``fresh`` says the first of them is
    the sequence's first. ``carry``: one of :data:`PIECE_CARRIES` (None: by
    platform). -> ``(y [T, h, d] float32, zero where no piece lies; (S,
    z))``."""
    carry = carry or PIECE_CARRIES[default_impl()]
    row0, length, slots, fresh, count = pieces
    c = cfg.retention_chunk_size
    t, dtype = q.shape[0], q.dtype
    # a window of c rows from any row0 < T stays inside the padded arrays
    q, k, v, gam = (jnp.pad(a, ((0, c),) + ((0, 0),) * (a.ndim - 1))
                    for a in (q, k, v, gam))
    window = lambda a, r0: jax.lax.dynamic_slice_in_dim(a, r0, c)  # noqa: E731

    def piece(i, state):
        *pools, y_all = state
        r0 = row0[i]
        valid = jnp.arange(c) < length[i]
        # ret_chunk inside ret_scan: the pieces' own time, apart from the
        # state step of the one-token rows beside them (decode_step)
        with scope("ret_scan"), scope("ret_chunk"):
            y, pools = _piece(
                window(q, r0),
                jnp.where(valid[:, None, None], window(k, r0), 0),
                window(v, r0),
                jnp.where(valid[:, None], window(gam, r0), 0),
                jnp.logical_not(fresh[i]).astype(jnp.float32),
                tuple(pools), layer, slots[i], cfg, dtype, carry)
            y_all = jax.lax.dynamic_update_slice_in_dim(
                y_all, jnp.where(valid[:, None, None], y, window(y_all, r0)),
                r0, 0)
        return (*pools, y_all)

    s_pool, z_pool, y_all = jax.lax.fori_loop(
        0, count, piece,
        (*pools, jnp.zeros((t + c, *q.shape[1:]), jnp.float32)))
    return y_all[:t], (s_pool, z_pool)
