"""``ops/kda.py``'s two entries at tiny sizes on the CPU: the state step's
``xla`` form and the Pallas kernel in interpret mode against each other; the
chunked form against the SEQUENTIAL float32 recurrence (the state step a
token at a time), a decay that overflows a naive ``e^-G`` among the cases;
and ``ops/ssm.py``'s one convolution, which the delta rule calls with no
bias."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _rows(t, h=4, d=16, seed=0, strong=False):
    """``(q, k, v, g, beta)`` as the mixer hands them over; ``strong``: -40
    a row on every fourth channel, so that a piece's running sum passes
    float32's exponent."""
    from deepspeedsyclsupport_tpu.ops.kda import l2norm

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = jax.random.normal(ks[0], (3, t, h, d))
    g = -jnp.exp(jax.random.uniform(ks[1], (t, h, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    if strong:
        g = jnp.where(jnp.arange(d) % 4 == 0, -40.0, g)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[2], (t, h)))
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
