"""``ops/sparse_block.py``'s three functions and the two routes of
``inference/v2/bsa.py`` against plain ``numpy`` twins written here, row by
row; and ``ops/ssm.py``'s two lightning entries against the sequential
recurrence. Tiny sizes (kernel 4 / stride 2 / block 8 / 6 blocks read of
which the first and a window of 2 / ``dense_len`` 72), float32, the CPU; the
kernels in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.ops import sparse_block as sb
from deepspeedsyclsupport_tpu.ops import ssm

SIZES = sb.Sizes(block=8, kernel=4, stride=2, init=1, window=2, topk=6,
                 dense_len=72)
H, KVH, D = 4, 2, 16


def keys_of(t, seed=0):
    return np.random.default_rng(seed).standard_normal((t, KVH, D)).astype(
        np.float32)


# -------------------------------------------------------------- numpy twins
def pooled_twin(k):
    return np.stack([k[2 * j:2 * j + 4].mean(0)
                     for j in range((len(k) - 4) // 2 + 1)]) \
        if len(k) >= 4 else np.zeros((0, KVH, D), np.float32)


def scores_twin(q, c, pos, blocks):
    """One row: q [H, D], c [W, KVH, D] -> [KVH, blocks]."""
    out = np.zeros((KVH, blocks), np.float32)
    seen = [j for j in range(len(c)) if 2 * j + 3 <= pos]
    if not seen:
        return out
    for g in range(KVH):
        p = np.zeros(len(c))
        for h in range(g * H // KVH, (g + 1) * H // KVH):
            logit = np.array([q[h] @ c[j, g] for j in seen]) / np.sqrt(D)
            e = np.exp(logit - logit.max())
            p[seen] += e / e.sum()
        for b in range(blocks):
            over = [j for j in range(len(c))
                    if 2 * j < 8 * b + 8 and 2 * j + 4 > 8 * b]
            out[g, b] = max((p[j] for j in over), default=0.0)
    return out


def select_twin(score, pos):
    """One row and group: score [blocks] -> the set of blocks read."""
    own = pos // 8
    seen = list(range(own + 1))
    if pos + 1 < 72:
        return set(seen)
    forced = {b for b in seen if b < 1 or b > own - 2}
    rest = sorted((b for b in seen if b not in forced),
                  key=lambda b: (-score[b], b))
    return forced | set(rest[:6 - len(forced)])


# ----------------------------------------------------------- pooled keys
@pytest.mark.parametrize("chunks", [(30,), (7, 9, 14), (1,) * 12 + (18,)])
def test_the_pool_holds_every_whole_window_however_the_rows_arrive(chunks):
    """A sequence's keys arrive in ``chunks`` (the windows that straddle
    two chunks and two pages among them) through pages that are NOT in
    order: the pool then holds the mean of every whole window at the page
    its window starts in."""
    total = sum(chunks)
    k = keys_of(total)
    table = jnp.asarray([[5, 2, 7, 0]])
    k_pool = jnp.zeros((1, 8 * 8, KVH, D))
    ck = jnp.zeros((1, 8, 4, KVH, D))
    at = 0
    for n in chunks:
        pos = jnp.arange(at, at + n)
        slots = table[0, pos // 8] * 8 + pos % 8
        k_pool = k_pool.at[0, slots].set(k[at:at + n])
        ck = sb.pool_write(ck, k_pool, 0, table, jnp.zeros(n, jnp.int32),
                           pos, jnp.ones(n, bool), SIZES)
        at += n
    want = pooled_twin(k)
    got = np.asarray(sb.seq_pooled_keys(ck, 0, table))[0]
    np.testing.assert_allclose(got[:len(want)], want, rtol=1e-6, atol=1e-6)
    assert not got[len(want):].any()        # nothing written past them
    # a pad row and a row on another line of the table write nothing here
    same = sb.pool_write(ck, k_pool, 0, table, jnp.asarray([0, 3]),
                         jnp.asarray([7, 7]), jnp.asarray([False, False]),
                         SIZES)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(ck))


# ------------------------------------------------------ scores, selection
@pytest.fixture(scope="module")
def tile():
    """Two tiles of 8 rows of two sequences (60 and 90 tokens cached), the
    second tile's last rows dead, and their twins' scores."""
    rng = np.random.default_rng(3)
    ctx = (60, 90)
    c_seq = np.zeros((2, 48, KVH, D), np.float32)
    for s, t in enumerate(ctx):
        w = pooled_twin(keys_of(t, seed=s))
        c_seq[s, :len(w)] = w
        c_seq[s, len(w):] = 9.0         # what a page holds past the windows
    q = rng.standard_normal((2, 8, H, D)).astype(np.float32)
    pos = np.stack([np.arange(52, 60), np.arange(82, 90)])
    qlen = np.asarray([8, 5])
    want = np.stack([[scores_twin(q[a, r], c_seq[a][:(ctx[a] - 4) // 2 + 1],
                                  pos[a, r], 12) for r in range(8)]
                     for a in range(2)])
    return q, c_seq, pos, qlen, want


def test_block_scores_are_the_twins(tile):
    q, c_seq, pos, qlen, want = tile
    got = np.asarray(sb.block_scores(
        jnp.asarray(q), jnp.asarray(c_seq), jnp.arange(2), jnp.asarray(pos),
        jnp.asarray(qlen), SIZES))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(got[1, :5], want[1, :5], rtol=2e-5, atol=1e-6)
    # a group's probabilities over its visible windows sum to its heads
    assert got.max() <= H // KVH + 1e-5
    # a dead tile costs nothing and scores nothing
    dead = sb.block_scores(jnp.asarray(q), jnp.asarray(c_seq), jnp.arange(2),
                           jnp.asarray(pos), jnp.zeros(2, jnp.int32), SIZES)
    assert not np.asarray(dead).any()


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_selection_is_the_twins_with_ties_to_the_lower_block(tile, impl):
    q, c_seq, pos, qlen, want = tile
    scores = want.copy()
    scores[1, :, 0, :] = 0.5         # one group's scores all tied
    sel = np.asarray(sb.select_blocks(
        jnp.asarray(scores), jnp.asarray(pos), jnp.asarray(qlen), SIZES,
        impl))
    for a in range(2):
        for r in range(8):
            for g in range(KVH):
                got = set(np.flatnonzero(sel[a, r, g]))
                if r >= qlen[a]:
                    assert not got
                    continue
                assert got == select_twin(scores[a, r, g], pos[a, r])
                assert {0, pos[a, r] // 8, pos[a, r] // 8 - 1} <= got
                # (the first tile's rows stand under dense_len)
                assert len(got) == (6 if a else pos[a, r] // 8 + 1)
    # rows either side of dense_len in one tile: every visible block under
    # it whatever the scores, six from it on
    across = np.asarray(sb.select_blocks(
        jnp.asarray(scores[:1]), jnp.asarray(pos[:1] + 15),
        jnp.asarray(qlen[:1]), SIZES, impl))
    for r in range(8):
        at = pos[0, r] + 15
        assert across[0, r].sum(-1).tolist() \
            == [at // 8 + 1 if at + 1 < 72 else 6] * KVH


def test_a_rows_page_table_is_its_selection_in_rising_order(tile):
    _q, _c, _pos, _qlen, want = tile
    pos = jnp.asarray([75, 89, -1, 20])
    scores = jnp.asarray(np.stack([want[0, 7], want[1, 7], want[0, 0],
                                   want[0, 0]]))[None]
    sel = sb.select_blocks(scores, pos[None], jnp.full((1,), 4, jnp.int32),
                           SIZES)[0]
    table = jnp.asarray(np.random.default_rng(1).permutation(48)[:48]
                        .reshape(4, 12).astype(np.int32))
    tables, lens = sb.page_tables(sel, table, pos, SIZES)
    assert tables.shape == (4 * KVH, SIZES.row_pages)
    for s in range(4):
        for g in range(KVH):
            blocks = np.flatnonzero(np.asarray(sel[s, g]))
            n = len(blocks)
            row = s * KVH + g
            assert np.asarray(tables[row, :n]).tolist() \
                == np.asarray(table[s])[blocks].tolist()
            want_len = 0 if pos[s] < 0 else (n - 1) * 8 + int(pos[s]) % 8 + 1
            assert int(lens[row]) == want_len
    assert int(lens[0]) == 5 * 8 + 4 and int(lens[6]) == 2 * 8 + 5
    assert SIZES.row_pages == 9


# --------------------------------------------------------- the two routes
def attention_twin(q, k, v, read, pos):
    """One row: q [H, D]; k, v [T, KVH, D]; ``read`` the blocks a group."""
    out = np.zeros((H, D), np.float32)
    for h in range(H):
        g = h // (H // KVH)
        keys = [s for s in range(pos + 1) if s // 8 in read[g]]
        logit = np.array([q[h] @ k[s, g] for s in keys]) / np.sqrt(D)
        w = np.exp(logit - logit.max())
        out[h] = (w / w.sum()) @ v[keys, g]
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_both_routes_attend_over_the_twins_selection(impl):
    """``bsa.ragged_attend`` over a batch that holds a chunk of 11 rows (two
    atoms under their blocks, the first two rows under ``dense_len``), a
    one-token row (its own page table) and pads, then ``bsa.decode_attend`` over two
    rows: each row's output is softmax attention over exactly the blocks
    the twins choose, every head under its own group's choice, and the
    counts are the selection's own."""
    from deepspeedsyclsupport_tpu.inference.v2 import bsa
    from deepspeedsyclsupport_tpu.inference.v2.model import PrefillAttnContext

    cfg = type("Cfg", (), dict(
        sparse_block_size=8, sparse_block_kernel=4, sparse_block_stride=2,
        sparse_block_init=1, sparse_block_window=2, sparse_block_topk=6,
        sparse_block_dense_len=72))
    rng = np.random.default_rng(5)
    ctx = (80, 85)                      # tokens cached WITH this batch's
    tables = jnp.asarray([[3, 9, 1, 12, 5, 7, 2, 10, 4, 11, 6, 8],
                          [20, 14, 22, 13, 19, 16, 23, 15, 18, 21, 17, 0]])
    k = [keys_of(t, seed=10 + s) for s, t in enumerate(ctx)]
    v = [keys_of(t, seed=20 + s) for s, t in enumerate(ctx)]
    k_pool = jnp.zeros((1, 24 * 8, KVH, D))
    v_pool = jnp.zeros((1, 24 * 8, KVH, D))
    ck = jnp.zeros((1, 24, 4, KVH, D))
    for s, t in enumerate(ctx):
        pos = jnp.arange(t)
        slots = tables[s, pos // 8] * 8 + pos % 8
        k_pool = k_pool.at[0, slots].set(k[s])
        v_pool = v_pool.at[0, slots].set(v[s])
        ck = sb.pool_write(ck, k_pool, 0, tables, jnp.full(t, s), pos,
                           jnp.ones(t, bool), SIZES)
    # rows 0..10: sequence 0's last 11 tokens; row 11: sequence 1's last
    token_seq = jnp.asarray([0] * 11 + [1] + [2] * 4)
    token_pos = jnp.asarray(list(range(69, 80)) + [84] + [0] * 4)
    q = jnp.asarray(rng.standard_normal((16, H, D)), jnp.float32)
    atom_qidx = jnp.asarray([list(range(8)), [8, 9, 10] + [15] * 5,
                             [15] * 8])
    ctx_args = PrefillAttnContext(
        k_cache=k_pool, v_cache=v_pool, layer=0, token_seq=token_seq,
        token_pos=token_pos, block_tables=tables, block_size=8, alibi=None,
        window=None, atom_qidx=atom_qidx,
        atom_pos0=jnp.asarray([69, 77, 0]), atom_qlen=jnp.asarray([8, 3, 0]),
        atom_tables=tables[jnp.asarray([0, 0, 0])],
        atom_inv=jnp.asarray(list(range(11)) + [16 + 7] * 5),
        dec_row=jnp.asarray([0, 11]), dec_len=jnp.asarray([0, 85]))
    name = {"xla": "xla", "pallas_interpret": "kernel_interpret"}[impl]
    out, pools = bsa.ragged_attend(
        q, (k_pool, v_pool, ck, jnp.zeros(7, jnp.int32)), 0, ctx_args, cfg,
        name)
    counted = dict(zip(bsa.COUNTS, np.asarray(pools[3]).tolist()))
    pairs = pages = 0
    for row in range(12):
        s, pos = int(token_seq[row]), int(token_pos[row])
        c = pooled_twin(k[s])
        score = scores_twin(np.asarray(q[row]), c, pos, 12)
        read = [select_twin(score[g], pos) for g in range(KVH)]
        want = attention_twin(np.asarray(q[row]), k[s], v[s], read, pos)
        np.testing.assert_allclose(np.asarray(out[row]), want, rtol=2e-4,
                                   atol=2e-5)
        pairs += sum(sum(min(8, pos + 1 - 8 * b) for b in r) for r in read)
        pages += sum(map(len, read))
    assert counted["bsa_rows"] == 12 and counted["bsa_pairs"] == pairs
    if impl == "xla":       # every row a tile of its own
        assert counted["bsa_pages"] == pages
    else:                   # an atom reads the union of its rows' pages
        assert counted["bsa_row_pages"] == 12 <= counted["bsa_pages"] <= pages
    assert counted["bsa_windows"] == sum(
        (int(p) + 1 - 4) // 2 + 1 for p in token_pos[:12])
    # decode_forward's rows: one a slot, the second slot's only
    lens = jnp.asarray([80, 85])
    qd = jnp.asarray(rng.standard_normal((2, H, D)), jnp.float32)
    out_d, pools_d = bsa.decode_attend(
        qd, (k_pool, v_pool, ck, jnp.zeros(7, jnp.int32)), 0, tables, lens,
        8, cfg, impl)
    for s in range(2):
        pos = ctx[s] - 1
        score = scores_twin(np.asarray(qd[s]), pooled_twin(k[s]), pos, 12)
        read = [select_twin(score[g], pos) for g in range(KVH)]
        np.testing.assert_allclose(
            np.asarray(out_d[s]),
            attention_twin(np.asarray(qd[s]), k[s], v[s], read, pos),
            rtol=2e-4, atol=2e-5)
    assert np.asarray(pools_d[3])[3] == 2 * KVH * 6     # 6 pages a group


@pytest.mark.parametrize("name", ["kernel", "kernel_interpret"])
def test_the_atoms_route_makes_no_array_of_keys(name):
    """``bsa.attend_atoms`` hands the ragged kernel the selection of BLOCKS
    (``[A, KVH, blocks, BQ]``) and the kernel widens a STEP's to its keys:
    nowhere in the traced route, inside the kernel's body or outside it,
    stands a value of ``atoms x KVH x BQ x keys`` elements (the mask PRs
    59-67 built, turned and padded in HBM: 380 MB a layer at the cell's
    widths), and no int8 value is larger than the selection itself."""
    from deepspeedsyclsupport_tpu.analysis.jaxpr_walk import iter_eqns
    from deepspeedsyclsupport_tpu.inference.v2 import bsa
    from deepspeedsyclsupport_tpu.inference.v2.dsa import kernel_impl

    atoms, bq, bps = 3, 8, 128     # a lane tile of blocks: no pad
    ctx = type("Ctx", (), dict(
        block_tables=jnp.zeros((2, bps), jnp.int32), block_size=8,
        atom_qidx=jnp.zeros((atoms, bq), jnp.int32),
        token_seq=jnp.zeros((16,), jnp.int32),
        atom_pos0=jnp.asarray([950, 958, 0]), atom_qlen=jnp.asarray([8, 3, 0]),
        atom_tables=jnp.zeros((atoms, bps), jnp.int32),
        atom_inv=jnp.zeros((16,), jnp.int32)))
    pool = jnp.zeros((1, 48 * 8, KVH, D))
    c_seq = jnp.zeros((2, bps * 4, KVH, D))
    jaxpr = jax.make_jaxpr(lambda q: bsa.attend_atoms(
        q, c_seq, pool, pool, 0, ctx, SIZES, kernel_impl(name)))(
            jnp.zeros((16, H, D)))
    keys = atoms * KVH * bq * bps * SIZES.block
    sel = atoms * KVH * bq * bps
    # every value the route makes, a kernel's body and a ``jit``'s too
    shapes = {(v.aval.shape, str(v.aval.dtype))
              for eqn, _ in iter_eqns(jaxpr.jaxpr) for v in eqn.outvars
              if hasattr(v.aval, "shape")}
    assert ((atoms, bq, KVH, bps), "int8") in shapes      # select_blocks'
    assert ((atoms, KVH, bps, bq), "int8") in shapes      # the kernel's
    assert not [s for s in shapes if np.prod(s[0]) >= keys], shapes
    assert not [s for s in shapes if s[1] == "int8"
                and np.prod(s[0]) > 2 * sel]


# ------------------------------------------------- lightning's two entries
def recurrence_twin(q, k, v):
    t, h, d = q.shape
    lam = np.exp(-np.exp2(-8.0 * (np.arange(h) + 1) / h))
    state, out = np.zeros((h, d, d)), []
    for i in range(t):
        state = lam[:, None, None] * state + np.einsum("hk,hv->hkv", k[i],
                                                       v[i])
        out.append(np.einsum("hkv,hk->hv", state, q[i]))
    return np.stack(out), state


@pytest.mark.parametrize("step", ["xla", "pallas_interpret"])
def test_lightnings_two_entries_are_the_sequential_recurrence(step):
    """29 tokens of one sequence: pieces of 8, 8 and 5 rows through the
    chunked form (the first from zeros whatever the slot held), then eight
    one-token rows through the state step beside a row of another slot and
    a pad on the sink; the state left is the recurrence's."""
    h, d, t = 4, 16, 29
    rng = np.random.default_rng(2)
    q, k, v = (rng.standard_normal((t, h, d)).astype(np.float32)
               for _ in range(3))
    want, state = recurrence_twin(q, k, v)
    pool = jnp.full((2, 4, h, d, d), 7.0)      # slot 1 holds junk: fresh
    pieces = (jnp.asarray([0, 8, 16, 0]), jnp.asarray([8, 8, 5, 0]),
              jnp.asarray([1, 1, 1, 3]),
              jnp.asarray([True, False, False, False]), jnp.asarray(3))
    got, pool = jax.jit(lambda *a: ssm.lightning_pieces(
        *a, 1, pieces, 8, jnp.float32))(*(jnp.asarray(x[:21])
                                          for x in (q, k, v)), pool)
    np.testing.assert_allclose(np.asarray(got), want[:21], rtol=2e-4,
                               atol=2e-5)
    fn = ssm.STATE_STEPS[step]
    for i in range(21, t):
        rows = lambda x: jnp.stack([jnp.asarray(x[i]), jnp.asarray(x[0]),
                                    jnp.asarray(x[1])])
        out, pool = ssm.lightning_step(
            rows(q), rows(k), rows(v), pool, 1, jnp.asarray([1, 2, 3]),
            jnp.asarray([False, i == 21, False]), fn)
        np.testing.assert_allclose(np.asarray(out[0]), want[i], rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(pool[1, 1]), state, rtol=2e-4,
                               atol=2e-5)
    assert (np.asarray(pool[0]) == 7.0).all()      # the other layer's pool
    # half-lives from under a token to some tens at 4 heads (0.8 to 177 at
    # the model's 32)
    life = np.log(2) / -np.asarray(ssm.lightning_decay(32))
    assert life.min() == pytest.approx(0.82, abs=0.01)
    assert life.max() == pytest.approx(177.4, abs=0.1)
