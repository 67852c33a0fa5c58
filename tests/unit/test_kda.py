"""``ops/kda.py``'s two entries at tiny sizes on the CPU: the state step's
``xla`` form and the Pallas kernel in interpret mode against each other; the
chunked form against the SEQUENTIAL float32 recurrence (the state step a
token at a time), a decay that overflows a naive ``e^-G`` among the cases;
the pieces' kernel (``kda_piece``) interpreted against both, the state's
hand-over from piece to piece among its cases; and ``ops/ssm.py``'s one
convolution, which the delta rule calls with no bias."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _rows(t, h=4, d=16, seed=0, strong=False, beta_shift=0.0):
    """``(q, k, v, g, beta)`` as the mixer hands them over; ``strong``: -40
    a row on every fourth channel, so that a piece's running sum passes
    float32's exponent; ``beta_shift`` 4: every write's strength at 1.93 -
    2.0, the transition's eigenvalue near -1."""
    from deepspeedsyclsupport_tpu.ops.kda import l2norm

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = jax.random.normal(ks[0], (3, t, h, d))
    g = -jnp.exp(jax.random.uniform(ks[1], (t, h, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    if strong:
        g = jnp.where(jnp.arange(d) % 4 == 0, -40.0, g)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[2], (t, h))
                                + beta_shift)
    return l2norm(q) * d ** -0.5, l2norm(k), v, g, beta


def _sequential(rows, pool, layer, slot_of, first_of):
    """The recurrence a token at a time through the XLA state step."""
    from deepspeedsyclsupport_tpu.ops import kda

    out = []
    for i in range(rows[0].shape[0]):
        y, pool = kda.decode_step(
            *(a[i:i + 1] for a in rows), pool, layer,
            jnp.asarray([slot_of(i)]), jnp.asarray([first_of(i)]), None,
            kda.STATE_STEPS["xla"])
        out.append(y[0])
    return jnp.stack(out), pool


def test_the_xla_and_the_pallas_interpret_steps_agree():
    """``decode_step`` over six rows on five slots (two padding rows share
    the sink, one row fresh): the same outputs and the same pool, and a
    fresh row starts from zeros whatever its slot held."""
    from deepspeedsyclsupport_tpu.ops import kda

    pool = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 4, 16, 16))
    rows = _rows(6, seed=1)
    slots = jnp.asarray([3, 0, 5, 5, 4, 1])
    fresh = jnp.asarray([False, True, False, False, False, False])
    got = {name: kda.decode_step(*rows, pool, 1, slots, fresh, None,
                                 kda.STATE_STEPS[name])
           for name in ("xla", "pallas_interpret")}
    (y, new), (y_k, new_k) = got["xla"], got["pallas_interpret"]
    live = np.asarray([0, 1, 3, 4, 5])         # rows not on the sink
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_k)[live],
                               atol=1e-5)
    np.testing.assert_allclose(new[:, :5], new_k[:, :5], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(pool)[0])
    assert not np.allclose(np.asarray(new)[1, 3], np.asarray(pool)[1, 3])
    # the fresh row: beta k (v)^T from zeros, o = S^T q
    q, k, v, _g, beta = (np.asarray(a)[1] for a in rows)
    s0 = beta[:, None, None] * k[:, :, None] * v[:, None, :]
    np.testing.assert_allclose(np.asarray(new)[1, 0], s0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[1],
                               np.einsum("hkv,hk->hv", s0, q), atol=1e-6)
    # the kernel takes its heads in groups of any divisor
    y_2, new_2 = kda._state_step_pallas(
        pool, 1, slots, (~fresh).astype(jnp.float32), *rows, interpret=True,
        heads=2)
    np.testing.assert_allclose(np.asarray(y_2)[live], np.asarray(y)[live],
                               atol=1e-5)
    np.testing.assert_allclose(new_2[:, :5], new[:, :5], atol=1e-5)


@pytest.mark.parametrize("strong", [False, True],
                         ids=["plain_decay", "decay_that_overflows_exp_-G"])
@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_form_is_the_sequential_recurrence(chunk, strong):
    """Three pieces of two sequences (one continuing from what its slot
    holds, one ragged) against the state step a token at a time. Under the
    strong decay a piece's running sum of log-decays reaches -320 and more:
    ``exp(-G)`` alone is inf in float32, and the form stays finite and
    right because no exponent is taken by itself."""
    from deepspeedsyclsupport_tpu.ops import kda

    cfg = types.SimpleNamespace(kda_chunk_size=chunk)
    tail = chunk // 3 + 1
    t = 2 * chunk + tail
    pool = jax.random.normal(jax.random.PRNGKey(7), (2, 6, 4, 16, 16))
    rows = _rows(t + 1, seed=2, strong=strong)
    pieces = (jnp.asarray([0, chunk, chunk + tail, 0, 0]),
              jnp.asarray([chunk, tail, chunk, 0, 0]),
              jnp.asarray([2, 2, 0, 5, 5]),
              jnp.asarray([True, False, False, False, False]),
              jnp.asarray(3))
    if strong:
        run = np.cumsum(np.asarray(rows[3])[:chunk], axis=0)
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-run.astype(np.float32))).any()
    y, new = kda.chunked(*rows, pool, 1, pieces, cfg)
    want, want_pool = _sequential(
        tuple(a[:t] for a in rows), pool, 1,
        lambda i: 2 if i < chunk + tail else 0, lambda i: i == 0)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y[:t], want, atol=2e-5)
    assert not np.asarray(y[t]).any()            # no piece lies there
    np.testing.assert_allclose(new[:, :5], want_pool[:, :5], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(pool)[0])


# a case of the pieces' kernel: the chunk, the heads, the pieces as (slot,
# length, fresh, rows skipped before it) in the order they are walked, the
# rows left after the last, and what the rows are drawn with; ``live``: the
# count handed over (None: every piece), ``heads``: heads a grid step
PIECE_CASES = {
    "lengths_1_17_64": dict(chunk=64, pieces=[(0, 64, True, 0),
                                              (0, 17, False, 0),
                                              (3, 1, False, 2)], after=3),
    "strong_decay": dict(chunk=16, pieces=[(2, 16, True, 0), (2, 6, False, 0),
                                           (0, 16, False, 1)], after=1,
                         rows=dict(strong=True)),
    "beta_near_2": dict(chunk=16, pieces=[(1, 16, True, 0), (1, 16, False, 0),
                                          (4, 9, False, 0)], after=2,
                        rows=dict(beta_shift=4.0)),
    "window_past_the_last_row": dict(chunk=16, pieces=[(0, 16, True, 0),
                                                       (1, 5, False, 0)],
                                     after=0),
    "fresh_over_a_dirty_slot": dict(chunk=8, pieces=[(3, 8, True, 0),
                                                     (3, 8, True, 0)],
                                    after=1),
    "three_of_one_slot_between_two_of_another": dict(
        chunk=8, pieces=[(1, 8, False, 0), (4, 8, False, 0), (4, 8, False, 0),
                         (4, 5, False, 0), (1, 8, False, 0)], after=0),
    "dead_pieces_beyond_the_count": dict(
        chunk=8, pieces=[(2, 8, True, 0), (2, 3, False, 0), (2, 8, False, 0),
                         (0, 8, False, 0)], after=0, live=2),
    "heads_the_step_does_not_divide": dict(
        chunk=8, heads=24, pieces=[(0, 8, True, 0), (0, 8, False, 0),
                                   (1, 4, False, 0)], after=1),
    "one_head_tile_a_step": dict(
        chunk=8, heads=16, step=8, pieces=[(0, 8, False, 0), (0, 2, False, 0),
                                           (2, 8, True, 3)], after=0),
}


@pytest.mark.parametrize("case", sorted(PIECE_CASES))
def test_the_pieces_kernel_is_the_sequential_recurrence(case):
    """``kda_piece`` interpreted against the state step a token at a time
    and against the ``xla`` form (a loop of ``_piece``): the outputs, the
    slots it wrote, and everything it must leave alone (the other layer,
    the slots of no live piece, ``y`` where no live piece lies)."""
    from deepspeedsyclsupport_tpu.ops import kda

    spec = PIECE_CASES[case]
    c, h = spec["chunk"], spec.get("heads", 4)
    cfg = types.SimpleNamespace(kda_chunk_size=c)
    row0, at = [], 0
    for _slot, length, _fresh, skipped in spec["pieces"]:
        row0.append(at + skipped)
        at += skipped + length
    t = at + spec["after"]
    slot, length, fresh, _ = (list(x) for x in zip(*spec["pieces"]))
    live = spec.get("live", len(row0))
    # two dead pieces behind the live ones, pointing at live rows and slots
    pieces = tuple(jnp.asarray(x + x[:1] * 2) for x in (row0, length, slot,
                                                         fresh)) \
        + (jnp.asarray(live),)
    pool = jax.random.normal(jax.random.PRNGKey(7), (2, 6, h, 16, 16))
    rows = _rows(t, h=h, seed=3, **spec.get("rows", {}))
    form = functools.partial(kda._chunked_pallas, interpret=True,
                             heads=spec.get("step"))
    y, new = jax.jit(lambda *a: kda.chunked(*a, 1, pieces, cfg, form))(
        *rows, pool)
    y_x, new_x = jax.jit(lambda *a: kda.chunked(
        *a, 1, pieces, cfg, kda.PIECES["xla"]))(*rows, pool)

    def token(i, carry):
        """Live row ``i`` (of the pieces' rows, in the order walked) through
        the XLA state step."""
        p, out = carry
        r = order[i]
        y_i, p = kda.decode_step(
            *(jax.lax.dynamic_slice_in_dim(a, r, 1) for a in rows), p, 1,
            slot_of[i][None], first[i][None], None, kda.STATE_STEPS["xla"])
        return p, jax.lax.dynamic_update_slice_in_dim(out, y_i, r, 0)

    walked = [(r0 + j, s, f and j == 0) for r0, n, s, f in
              list(zip(row0, length, slot, fresh))[:live] for j in range(n)]
    order, slot_of, first = (jnp.asarray(x) for x in zip(*walked))
    want_pool, want = jax.jit(lambda p: jax.lax.fori_loop(
        0, len(walked), token, (p, jnp.zeros(rows[2].shape))))(pool)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_allclose(y, y_x, atol=2e-5)
    np.testing.assert_allclose(new, want_pool, atol=2e-5)
    np.testing.assert_allclose(new, new_x, atol=2e-5)
    quiet = np.setdiff1d(np.arange(t), np.asarray(order))
    assert not np.asarray(y)[quiet].any()
    untouched = np.setdiff1d(np.arange(6), np.asarray(slot[:live]))
    np.testing.assert_array_equal(np.asarray(new)[1, untouched],
                                  np.asarray(pool)[1, untouched])
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(pool)[0])


def test_one_convolution_for_both_mixers():
    """``ops/ssm.py``'s convolution with no bias (the delta rule's): the
    pieces' loop and the one-token step give what a plain causal
    convolution over the whole sequence gives, the tail carried between
    them."""
    from deepspeedsyclsupport_tpu.ops import ssm

    t, ch, kw, chunk = 21, 24, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x, w = jax.random.normal(ks[0], (t, ch)), jax.random.normal(ks[1],
                                                                (kw, ch))
    conv = jax.random.normal(ks[2], (2, kw - 1, 3, ch))   # junk in the slots
    pieces = (jnp.asarray([0, 8, 16, 0]), jnp.asarray([8, 8, 4, 0]),
              jnp.asarray([1, 1, 1, 2]),
              jnp.asarray([True, False, False, False]), jnp.asarray(3))
    out, conv = ssm.conv_pieces(x, w, None, conv, 1, pieces, chunk)
    last, conv = ssm.conv_step(x[20:21], w, None, conv, 1, jnp.asarray([1]),
                               jnp.asarray([True]))
    before = jnp.pad(x, ((kw - 1, 0), (0, 0)))
    want = jax.nn.silu(sum(w[j] * before[j:j + t] for j in range(kw)))
    np.testing.assert_allclose(out[:20], want[:20], atol=1e-5)
    np.testing.assert_allclose(last[0], want[20], atol=1e-5)
    np.testing.assert_allclose(conv[1, :, 1], x[18:21], atol=1e-6)
