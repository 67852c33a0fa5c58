"""``ops/ssm.conv_pieces``: the depthwise convolution of the pieces of a flat
batch, its tail in the pool. The kernel (``conv_pieces``, interpreted) against
the XLA loop AND against a plain sequential convolution of each whole
sequence: the results, the tails the pieces leave, every other slot and the
sink bit for bit, zeros where no piece lies."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.ops import ssm

KW, CHUNK, LAYER = 4, 8, 1


def table(pieces, places, sink):
    """``pieces`` [(row0, length, slot, fresh)] as the kernel takes them,
    on ``places`` places: the dead ones behind, on the sink."""
    dead = [(0, 0, sink, False)] * (places - len(pieces))
    row0, length, slot, fresh = zip(*(list(pieces) + dead))
    return (jnp.asarray(row0, jnp.int32), jnp.asarray(length, jnp.int32),
            jnp.asarray(slot, jnp.int32), jnp.asarray(fresh, bool),
            jnp.asarray(len(pieces), jnp.int32))


def whole(x, w, bias, pool, pieces):
    """The plain convolution of each whole sequence: ``(out, {slot: the
    last KW - 1 inputs})``, a sequence that is not fresh behind what its
    slot holds."""
    f32 = jnp.float32
    x = x.astype(pool.dtype).astype(f32)
    out, tails = np.zeros(x.shape, np.float32), {}
    for row0, n, slot, fresh in pieces:
        before = jnp.zeros((KW - 1, x.shape[1]), f32) if fresh else \
            tails.get(slot, pool[LAYER, :, slot].astype(f32))
        ext = jnp.concatenate([before, x[row0:row0 + n]])
        acc = sum(w[j] * ext[j:j + n] for j in range(KW))
        out[row0:row0 + n] = jax.nn.silu(acc if bias is None else bias + acc)
        tails[slot] = ext[n:n + KW - 1]
    return out, tails


def operands(t, ch, total, dtype, bias, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (t, ch)).astype(dtype)
    w = jax.random.normal(ks[1], (KW, ch))
    b = jax.random.normal(ks[2], (ch,)) if bias else None
    pool = jax.random.normal(ks[3], (3, KW - 1, total, ch)).astype(dtype)
    return x, w, b, pool                            # junk in every slot


@functools.lru_cache(maxsize=None)
def program(name, chunk, how):
    """``conv_pieces`` through one form, jitted ONCE for every case of one
    shape: the pieces are arguments, on PLACES places."""
    form = ssm.CONV_PIECES["xla"] if name == "xla" else functools.partial(
        ssm._conv_pieces_pallas, interpret=True, **dict(how))
    return jax.jit(lambda x, w, b, pool, tab: ssm.conv_pieces(
        x, w, b, pool, LAYER, tab, chunk, form))


PLACES = 8


def hold(pieces, t=40, ch=24, total=33, dtype=jnp.float32, bias=False,
         places=PLACES, chunk=CHUNK, **how):
    """Both forms over ``pieces`` against the whole sequences'."""
    x, w, b, pool = operands(t, ch, total, dtype, bias)
    tab = table(pieces, places, total - 1)
    want, tails = whole(x, w, b, pool, pieces)
    got = {}
    for name in ("xla", "kernel"):
        out, new = program(name, chunk, tuple(sorted(how.items())))(
            x, w, b, pool, tab)
        assert out.shape == x.shape and out.dtype == jnp.float32
        np.testing.assert_allclose(out, want, atol=2e-5, err_msg=name)
        lies = np.zeros((t,), bool)
        for row0, n, *_ in pieces:
            lies[row0:row0 + n] = True
        assert not np.asarray(out)[~lies].any(), name
        for slot, tail in tails.items():
            np.testing.assert_array_equal(
                np.asarray(new[LAYER, :, slot].astype(jnp.float32)),
                np.asarray(tail), err_msg=name)
        # the sink, every slot no piece names and the other layers: bit for
        # bit
        named = np.zeros((total,), bool)
        named[list(tails)] = True
        same = np.asarray(new == pool)
        assert same[:, :, ~named].all() and same[[0, 2]].all(), name
        got[name] = (out, new)
    np.testing.assert_allclose(got["kernel"][0], got["xla"][0], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["kernel"][1]),
                                  np.asarray(got["xla"][1]))


@pytest.mark.parametrize("fresh", [True, False])
@pytest.mark.parametrize("n", [1, 2, 3, CHUNK - 1, CHUNK])
def test_a_piece_of_any_length_behind_its_slots_tail(n, fresh):
    """One piece of 1 .. ``chunk`` rows at a row no tile starts at: behind
    zeros where it is fresh, behind what the slot holds where it is not
    (of which ``kernel - 1 - n`` rows survive a piece shorter than that)."""
    hold([(5, n, 18, fresh)])


@pytest.mark.parametrize("lengths", [(CHUNK, 5), (CHUNK, CHUNK, 2), (2, 1, 1)])
def test_consecutive_pieces_of_one_slot_hand_the_tail_on(lengths):
    """A chunk longer than a piece is consecutive pieces of ONE slot: each
    stands behind the rows just before it, through the held block."""
    pieces, row = [], 3
    for i, n in enumerate(lengths):
        pieces.append((row, n, 7, i == 0))
        row += n
    hold(pieces)


@pytest.mark.parametrize("slots", [(4, 5), (2, 13), (15, 16), (3, 20, 4)])
def test_pieces_of_slots_of_one_block_and_of_its_neighbour(slots):
    """Two pieces in different slots of one 16-slot block, adjacent and
    not, read the block as the earlier one left it; slots 15 and 16, and
    20 between 3 and 4, change the block and come back to it."""
    pieces = [(2 + 9 * i, 3 + 2 * i, s, i == 1) for i, s in enumerate(slots)]
    hold(pieces)


def test_a_frame_that_would_pass_the_end_of_the_batch_starts_earlier():
    """The last piece's frame starts at ``T - frame``: its rows stand a
    few rows in, behind a one-token row that is nobody's."""
    hold([(0, 4, 1, True), (27, CHUNK, 2, False), (36, 4, 2, False)], t=40)


@pytest.mark.parametrize("count", ["none", "all"])
def test_the_walk_ends_at_the_count(count):
    """No live piece: nothing moves. Every place live: none is skipped."""
    if count == "none":
        return hold([])
    pieces = [(CHUNK * i, CHUNK, i % 3, i < 3) for i in range(5)]
    hold(pieces, places=5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("bias", [True, False])
def test_both_pools_with_and_without_a_bias(bias, dtype):
    """Nemotron's convolution has a bias and Solar's has none; the pool is
    bfloat16 in both cells and float32 in the small models: a ragged round
    of five sequences, one-token rows between them."""
    hold([(1, CHUNK, 30, True), (9, 6, 30, False), (16, 2, 31, False),
          (19, 1, 0, False), (21, CHUNK, 14, True), (29, CHUNK, 14, False),
          (37, 3, 14, False)], dtype=dtype, bias=bias)


@pytest.mark.parametrize("how", [
    dict(ch=200), dict(ch=256, channels=128, lanes=128), dict(ch=512),
    dict(total=5), dict(total=17), dict(total=21), dict(chunk=16, t=70),
    dict(chunk=12, t=33)])
def test_every_width_pool_and_chunk(how):
    """Channels that 128 does not divide (one strip of all of them), two
    grid steps of one strip and one of two; a pool smaller than a block
    (held whole), of one block and the sink, and one whose slots pass its
    last whole block (no aligned copy holds them: the XLA loop's); a chunk
    of two tiles and one that is no whole tile."""
    total = how.get("total", 33)
    chunk = how.get("chunk", CHUNK)
    hold([(2, chunk, 1 % (total - 1), True), (2 + chunk, 3, 1 % (total - 1),
                                              False),
          (6 + chunk, 5, 3 % (total - 1), False)],
         **{"bias": True, "dtype": jnp.bfloat16, **how})


def test_a_piece_on_the_sink_reads_zeros_and_leaves_nothing():
    """As the tail's kernel has it: padding's slot is nobody's."""
    x, w, b, pool = operands(24, 24, 17, jnp.float32, False)
    tab = table([(0, 5, 16, False), (8, 4, 2, False)], 3, 16)
    out, new = ssm.CONV_PIECES["pallas_interpret"](x, w, b, pool, LAYER, tab,
                                                   CHUNK)
    want, _ = whole(x, w, b, pool, [(0, 5, 16, True), (8, 4, 2, False)])
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new[:, :, 16]),
                                  np.asarray(pool[:, :, 16]))


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
def test_the_pieces_then_the_one_token_rows_on_one_pool(form):
    """``tests/unit/test_kda.py``'s shape through both forms: three pieces
    of a sequence, then its next token through ``conv_step``."""
    t, ch = 21, 24
    x, w, _, pool = operands(t, ch, 17, jnp.float32, False, seed=3)
    tab = table([(0, 8, 1, True), (8, 8, 1, False), (16, 4, 1, False)], 4, 16)
    out, pool = ssm.conv_pieces(x, w, None, pool, LAYER, tab, CHUNK,
                                ssm.CONV_PIECES[form])
    last, pool = ssm.conv_step(x[20:21], w, None, pool, LAYER,
                               jnp.asarray([1]), jnp.asarray([True]),
                               ssm.CONV_STEPS[form])
    before = jnp.pad(x, ((KW - 1, 0), (0, 0)))
    want = jax.nn.silu(sum(w[j] * before[j:j + t] for j in range(KW)))
    np.testing.assert_allclose(out[:20], want[:20], atol=1e-5)
    np.testing.assert_allclose(last[0], want[20], atol=1e-5)
    np.testing.assert_allclose(pool[LAYER, :, 1], x[18:21], atol=1e-6)


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
def test_the_chunked_scan_and_the_entry_give_one_convolution(form):
    """Mamba-2's mixed path keeps its convolution inside its own loop
    (``conv_piece`` a piece): two pieces of one sequence and one of another
    give what the sequences give a token at a time through ``decode_step``,
    the state and the tail included, and ``conv_pieces`` over the same
    pieces, in either form, leaves the same tails."""
    cfg = types.SimpleNamespace(
        ssm_d_inner=16, ssm_n_groups=2, ssm_state_size=4, mamba_num_heads=4,
        mamba_head_dim=4, ssm_chunk_size=CHUNK)
    ch, h, t, total = 16 + 2 * 2 * 4, 4, 20, 17
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    p = {"conv_w": jax.random.normal(ks[0], (KW, ch)),
         "conv_b": jax.random.normal(ks[1], (ch,)),
         "dt_bias": jax.random.normal(ks[2], (h,)),
         "A_log": 0.3 * jax.random.normal(ks[3], (h,)),
         "D": jax.random.normal(ks[4], (h,))}
    xbc = jax.random.normal(ks[5], (t, ch))
    dt = jax.random.normal(ks[6], (t, h))
    state = jnp.zeros((2, total, 2, 4, 8))
    conv = jax.random.normal(ks[7], (2, KW - 1, total, ch))
    tab = table([(0, CHUNK, 3, True), (CHUNK, 4, 3, False), (13, 7, 9, True)],
                4, total - 1)
    y, s_c, c_c = jax.jit(lambda *a: ssm.chunked_scan(
        *a, 1, tab, cfg))(xbc, dt, p, state, conv)
    _, c_p = ssm.conv_pieces(xbc, *ssm._conv_weights(p), conv, 1, tab, CHUNK,
                             ssm.CONV_PIECES[form])
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_c))
    s_d, c_d, rows = state, conv, []
    for i in range(t):
        slot = 3 if i < 12 else 9
        row, s_d, c_d = ssm.decode_step(
            xbc[i:i + 1], dt[i:i + 1], p, s_d, c_d, 1, jnp.asarray([slot]),
            jnp.asarray([i in (0, 13)]), cfg, ssm.STATE_STEPS["xla"],
            ssm.CONV_STEPS["xla"])
        rows.append(row[0] if i != 12 else jnp.zeros_like(row[0]))
    np.testing.assert_allclose(y, jnp.stack(rows), atol=2e-4)
    np.testing.assert_allclose(s_c[1, 3], s_d[1, 3], atol=2e-4)
    np.testing.assert_allclose(c_c[1, :, 3], xbc[9:12], atol=1e-6)
    np.testing.assert_allclose(c_c[1, :, 9], c_d[1, :, 9], atol=1e-6)


def test_the_platform_chooses_the_form(monkeypatch):
    """``conv_pieces`` takes the XLA form off the TPU and the kernel on it
    (no setting), and the serving forwards resolve theirs through the
    registry's kind ``conv_pieces``, as the tail's kernel is resolved."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg

    x, w, b, pool = operands(16, 24, 5, jnp.float32, False)
    tab = table([(0, 4, 1, True)], 2, 4)
    heard = []

    def listening(name):
        def form(*a):
            heard.append(name)
            return ssm._conv_pieces_xla(*a)
        return form

    monkeypatch.setattr(ssm, "CONV_PIECES",
                        {name: listening(name) for name in ssm.CONV_PIECES})
    ssm.conv_pieces(x, w, b, pool, LAYER, tab, CHUNK)
    monkeypatch.setattr(ssm, "default_impl", lambda: "pallas")
    ssm.conv_pieces(x, w, b, pool, LAYER, tab, CHUNK)
    M._conv_pieces_fn()(x, w, b, pool, LAYER, tab, CHUNK)
    reg.get_impl("conv_pieces", "pallas_interpret").fn(
        x, w, b, pool, LAYER, tab, CHUNK)
    assert heard == ["xla", "pallas", "xla", "pallas_interpret"]
