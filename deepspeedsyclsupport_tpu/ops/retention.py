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
  updated IN PLACE. On the TPU a Pallas kernel (``ret_state_step``) over the
  pool's own ``[layer, slot, kv head]`` (scalar-prefetch indices, the pool
  aliased to the output and left in HBM): a grid step copies one KV head's
  WHOLE state into VMEM (4.26 MB contiguous at ``d`` 128), walks it once in
  place in compute tiles (a tile of the new state is made, stored and read
  out for the group's ``h / hk`` queries while it is in registers; the
  features of a diagonal are made there from ``q`` and ``k``, a lane rotation
  and a product) and copies it back, with ONE direction of copy on the
  memory's bus at a time and the copy out in thirteen side by side. What the
  chip said of it (v5e, 16 rows x 8 heads, kernel alone; PERF.md section 6,
  PR 50): the BlockSpec pipeline's copy in and copy out at once move 638
  GB/s whatever the block (1,664 or 8,320 features) and whatever the body
  (78 % of 819; the update with no read-out the same), a copy in alone 712, a
  copy out alone 610; taking turns 82 %, the copy out in thirteen 85.4 %, the
  features made here and not read 87 % (1.695 -> 1.525 ms a layer). A head
  whose two buffers VMEM does not hold is refused. ``xla``: gather, update,
  scatter (the CPU tests' reference, and what the kernel is held against).
  ``z`` is 1/128 of the bytes and stays in XLA for both.
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
from .ssm import _divisor, default_impl

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
def _state_step_xla(pool, layer, slots, decay, qs, k, v):
    """``pool[layer, slots]`` one step on: gather, update, scatter (a
    scatter on the loop-carried pool is in place; the gather is a copy).
    ``decay`` [rows, hk] float32: 0 starts the row from zeros. ``qs`` [rows,
    hk, g, d] (scaled), ``k`` / ``v`` [rows, hk, d] float32. -> ``(phi(q)^T
    S [rows, hk, g, d], pool)``."""
    state = pool[layer, slots] * decay[:, :, None, None]
    new = state + v[:, :, :, None] * phi(k)[:, :, None, :]
    y = jnp.einsum("rjvf,rjgf->rjgv", new, phi(qs), precision=HIGHEST)
    return y, pool.at[layer, slots].set(new.astype(pool.dtype))


def _state_step_kernel(layer_ref, slots_ref, x_ref, pool_ref, y_ref, out_ref,
                       buf, rsem, wsem, *, group):
    """Grid step ``s`` of ``(rows, kv heads)``: one KV head's whole state
    ``[d, D]``, brought into one of two VMEM buffers, walked ONCE in place
    (:func:`_walk`) and sent back, with ONE direction of copy on the
    memory's bus at a time: head ``s - 1`` goes out while head ``s`` is
    computed, and only when it is out does head ``s + 1`` come in (into the
    buffer ``s - 1`` left). The pool's refs are the arrays in HBM."""
    hk = pl.num_programs(1)
    s = pl.program_id(0) * hk + pl.program_id(1)
    last = pl.num_programs(0) * hk - 1
    d, wide = buf.shape[1:]
    strips = wsem.shape[1]
    cut = wide // strips              # lanes of one of a head's copies out
    tail = 8 if d > 8 and d % 8 == 0 else d // 2   # rows of a read's tail

    def head(ref, step):
        return ref.at[layer_ref[0], slots_ref[jax.lax.div(step, hk)],
                      jax.lax.rem(step, hk)]

    def reads(step, k):
        """A head IN, in two copies: the rows above ``tail`` and the tail,
        each on a semaphore of its own."""
        src = head(pool_ref, step)
        return [pltpu.make_async_copy(src.at[rows], buf.at[k, rows],
                                      rsem.at[k, i])
                for i, rows in enumerate((pl.ds(0, d - tail),
                                          pl.ds(d - tail, tail)))]

    def writes(step, k):
        """The copies a head goes OUT in, side by side on the lanes, each on
        a semaphore of its own."""
        dst = head(out_ref, step)
        return [pltpu.make_async_copy(
            buf.at[k, :, pl.ds(i * cut, cut)], dst.at[:, pl.ds(i * cut, cut)],
            wsem.at[k, i]) for i in range(strips)]

    def start(dmas):
        for c in dmas:
            c.start()

    def wait(dmas):
        for c in dmas:
            c.wait()

    # The ORDER is the semaphores', not the clock's. Head ``s + 1`` comes
    # into the buffer head ``s - 1`` goes out of, so its copies in start
    # only after the wait on EVERY copy out of that buffer (starting them a
    # strip early read the same on the chip, 1.526 | 1.524 ms a layer, and
    # had nothing but time between a strip's read and the copy in over it).
    # The other way there is no buffer to share: head ``s - 1`` starts out
    # of buffer ``1 - k`` while the 8-row tail of head ``s`` is still on its
    # way into buffer ``k``, so that the bus is not idle while the copies
    # out get going (2.3 % of the kernel: PERF.md section 6, PR 50), and
    # the walk waits for the tail. The descriptors are made once a grid step
    # (tracing them is what set-up pays for them).
    k = jax.lax.rem(s, 2)
    coming = reads(s, k)
    then = reads(jnp.minimum(s + 1, last), 1 - k)
    going = writes(jnp.maximum(s - 1, 0), 1 - k)
    flush = writes(s, k)

    @pl.when(s == 0)
    def _():
        start(coming)

    wait(coming[:1])

    @pl.when(s > 0)
    def _():
        start(going)

    wait(coming[1:])
    _walk(x_ref, buf.at[k], y_ref, group)

    @pl.when(s > 0)
    def _():
        wait(going)

    @pl.when(s < last)
    def _():
        start(then)

    @pl.when(s == last)
    def _():
        start(flush)
        wait(flush)


def _walk(x_ref, st_ref, y_ref, group):
    """``st_ref`` [d, D], a head's state, one step on IN PLACE, in compute
    tiles of ``[COMPUTE_ROWS, d]`` (a band of the value's rows x one
    diagonal): a tile of the new state is made, stored and read out for all
    of the group's queries while it is in registers, the read-out summed
    lane for lane in accumulators the loop carries and reduced over the
    lanes once at the end. ``x`` [8k, d]: rows ``0 .. group - 1`` the scaled
    queries, then the key, the value and the decay on every lane; the
    FEATURES of a diagonal are made here, a lane rotation and a product for
    all of the rows at once (as :func:`_carry_kernel` makes them): ``[rows,
    features]`` never exists."""
    f32 = jnp.float32
    d = st_ref.shape[0]
    x = x_ref[...]
    decay = x[group + 2:group + 3, :]
    # the value down the sublanes, on every lane
    column = jnp.broadcast_to(x[group + 1:group + 2, :], (d, d)).T
    rows = min(COMPUTE_ROWS, d)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, Y_LANES), 1)
    for r0 in range(0, d, rows):
        band = slice(r0, r0 + rows)
        acc = _walk_band(x, decay, column[band, :], st_ref, band, group)
        part = jnp.zeros((rows, Y_LANES), f32)
        for j, a in enumerate(acc):
            part = jnp.where(lane == j, jnp.sum(a, axis=1, keepdims=True),
                             part)
        y_ref[band, :] = part


def _walk_band(x, decay, v, st_ref, band, group):
    """Rows ``band`` of :func:`_walk`'s head over all of its diagonals ->
    the group's accumulators ``[rows, d]``. A turn of the rolled loop is
    :data:`COMPUTE_DIAGONALS` diagonals (the most that divide the head's:
    five of the cell's 65), each one's rotation made from ``x`` itself: a
    rotation is ~100 cycles before its result is there, and only rotations
    that do not wait for one another hide it (PERF.md section 6, PR 50). The
    turn is traced ONCE and unrolled when the kernel is lowered (every
    traced copy of the tile is paid at every set-up)."""
    f32 = jnp.float32
    d = x.shape[-1]
    diagonals = st_ref.shape[1] // d
    count = _divisor(diagonals, COMPUTE_DIAGONALS)

    def diagonal(o, acc):
        w = jnp.where((o == 0) | (o == d // 2), 1.0, np.sqrt(2.0)).astype(f32)
        feat = x * pltpu.roll(x, jax.lax.rem(d - o, d), 1) * w
        at = pl.ds(pl.multiple_of(o * d, d), d)
        new = st_ref[band, at].astype(f32) * decay \
            + v * feat[group:group + 1, :]
        st_ref[band, at] = new.astype(st_ref.dtype)
        return tuple(a + new * feat[j:j + 1, :] for j, a in enumerate(acc))

    def turn(t, acc):
        return jax.lax.fori_loop(
            0, count, lambda i, acc: diagonal(t * count + i, acc), acc,
            unroll=count)

    return jax.lax.fori_loop(0, diagonals // count, turn,
                             (jnp.zeros(v.shape, f32),) * group)


# What the state's two buffers of a grid step may take of VMEM: one KV head's
# whole state at the cell's widths ([128, 8320] float32, 4.26 MB CONTIGUOUS in
# the pool) takes 8.5 MB of it, and a head they do not hold is refused.
# Smaller blocks lose: a block costs ~0.75 us beside its bytes (at 1,664
# features the kernel read 68 % of 819 GB/s, at the whole head 87 %, and
# PR 49's BlockSpec kernel 78 % at either: PERF.md section 6, PR 50).
STEP_VMEM_BYTES = 32 << 20
# lanes of one of the copies a head goes OUT in, side by side: one copy of
# the whole head writes at 610 GB/s, thirteen of 640 lanes at ~700 (PR 50's
# sweep: 1 / 5 / 13 / 65 copies 1.581 / 1.555 / 1.525 / 1.579 ms a layer); a
# head these do not divide goes out in one
WRITE_LANES = 640
# the tile of a head the kernel computes at a time: a band of the value's
# rows x one diagonal (8 registers of state beside the group's accumulators,
# 5 x 8), and the diagonals a turn of the rolled loop walks. Swept with the
# kernel alone (ms a layer; the copies' own floor 1.510): 32 | 64 | 128 rows
# at one diagonal a turn 4.00 | 2.90 | 2.41, at five 1.760 | 1.525 | 1.549,
# at thirteen 1.514 each; five at 64 rows is the least code that hides under
# the copies, and the code is traced at every set-up.
COMPUTE_ROWS = 64
COMPUTE_DIAGONALS = 5
Y_LANES = 128       # the read-out's block: query i in lane i


def _state_step_pallas(pool, layer, slots, decay, qs, k, v, interpret=False):
    """The same step with the pool aliased to the output and left in HBM:
    the kernel copies head ``[layer, slots[row], head]`` in and the same
    head out itself. Rows that share a slot (the sink) write it one after
    another; nobody reads it (a row may read it as the row before last left
    it)."""
    rows, hk, d, dim = (slots.shape[0], *pool.shape[2:])
    g = qs.shape[2]
    assert g <= Y_LANES, g
    held = 2 * d * dim * pool.dtype.itemsize
    assert held <= STEP_VMEM_BYTES, (
        f"two buffers of a head's state [{d}, {dim}] take {held} bytes of "
        f"VMEM, over STEP_VMEM_BYTES = {STEP_VMEM_BYTES}")
    pad = -(g + 3) % 8
    x = jnp.concatenate(
        [qs, k[:, :, None], v[:, :, None],
         jnp.broadcast_to(decay[:, :, None, None], (rows, hk, 1, d)),
         jnp.zeros((rows, hk, pad, d), jnp.float32)], axis=2)
    strips = dim // WRITE_LANES if dim % WRITE_LANES == 0 else 1
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(rows, hk),
        in_specs=[pl.BlockSpec((None, None, g + 3 + pad, d),
                               lambda r, j, *_: (r, j, 0, 0)),
                  in_hbm],
        out_specs=[pl.BlockSpec((None, None, d, Y_LANES),
                                lambda r, j, *_: (r, j, 0, 0)),
                   in_hbm],
        scratch_shapes=[pltpu.VMEM((2, d, dim), pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SemaphoreType.DMA((2, strips))])
    y, pool = pl.pallas_call(
        functools.partial(_state_step_kernel, group=g),
        out_shape=[jax.ShapeDtypeStruct((rows, hk, d, Y_LANES), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count the scalar-prefetch two: the pool is the 4th
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name="ret_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      x, pool)
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
    qs = q.astype(f32).reshape(rows, hk, h // hk, d) * _scale(cfg)
    k = k.astype(f32)
    z = z_pool[layer, slots] * decay[:, :, None] + phi(k)
    z_pool = z_pool.at[layer, slots].set(z.astype(z_pool.dtype))
    den = jnp.einsum("rjf,rjgf->rjg", z, phi(qs), precision=HIGHEST)
    num, s_pool = step(s_pool, layer, slots, decay, qs, k, v.astype(f32))
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
