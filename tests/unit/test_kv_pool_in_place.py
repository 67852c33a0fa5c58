"""The serving forwards keep the KV pool in place (ISSUE 25).

``decode_forward`` / ``ragged_forward`` carry the pool [L, slots, KVH, D]
through their layer loop and hand it whole, with the layer index, to the
attention impls; before, the pool was the scan's xs/ys and XLA sliced,
copied and wrote back one whole layer of it per layer for a few new rows.

* frozen case: both forwards return what the parent commit returned
  (``data/kv_pool_forward_golden.npz``, written by running THIS file as a
  script against a checkout of the commit to freeze:
  ``PYTHONPATH=<checkout> python tests/unit/test_kv_pool_in_place.py``) —
  logits, the rows written, every other row bit-identical;
* structure: compiled with a pool far larger than everything else, neither
  forward needs a temporary the size of one layer's K + V, and the donated
  pool is the output (a count from ``memory_analysis()``, not a time).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import BlockedKV
from deepspeedsyclsupport_tpu.inference.v2.ragged import (SequenceDescriptor,
                                                          build_ragged_batch)
from deepspeedsyclsupport_tpu.models import build_model, get_config

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "kv_pool_forward_golden.npz")
BS, BPS, S, T, BQ = 8, 4, 3, 16, 8     # block size, blocks/seq, slots, tokens
NUM_BLOCKS = S * BPS + 1
# (decode impl, prefill impl) pairs the frozen case runs under
IMPLS = {"xla": ("xla", "xla"),
         "pallas_interpret": ("pallas_interpret", "kernel_interpret")}


def _model(num_layers=3, **kw):
    cfg = dataclasses.replace(get_config("tiny"), dtype="float32",
                              num_layers=num_layers, **kw)
    model = build_model(cfg)
    return model, M.serving_layout(model.init_params(), model.config)


def _pool(cfg, num_slots, seed=0):
    """A pool of seeded noise: rows no forward writes must come back as is."""
    shape = (cfg.num_layers, num_slots, cfg.num_kv_heads, cfg.head_dim)
    k, v = jax.random.split(jax.random.PRNGKey(seed))
    return BlockedKV(jax.random.normal(k, shape, jnp.float32),
                     jax.random.normal(v, shape, jnp.float32))


# three sequences over disjoint, out-of-order blocks: 11, 9 and 0 tokens cached
TABLES = np.asarray([[5, 2, 9, 0], [7, 11, 1, 3], [4, 6, 8, 10]], np.int32)
CACHED = np.asarray([11, 9, 0], np.int32)


def _slots(seq, positions):
    positions = np.asarray(positions)
    return TABLES[seq, positions // BS] * BS + positions % BS


def _decode_case(decode_impl):
    """Slots 0 and 1 decode one token each, slot 2 is idle."""
    model, params = _model()
    kv = _pool(model.config, NUM_BLOCKS * BS)
    tokens = jnp.asarray([17, 230, 0], jnp.int32)
    active = jnp.asarray([True, True, False])
    logits, new = M.decode_forward(
        model, params, kv, tokens, jnp.asarray(CACHED), jnp.asarray(TABLES),
        active, block_size=BS, attn_impl=decode_impl)
    written = np.concatenate([_slots(0, [11]), _slots(1, [9])])
    return kv, {"logits": logits[:2]}, new, written


def _ragged_forward(model, params, kv, b, impl):
    """``ragged_forward`` over batch ``b``; the XLA path takes no tiles."""
    return M.ragged_forward(
        model, params, kv, *(jnp.asarray(a) for a in (
            b.tokens, b.token_seq, b.token_pos, b.block_tables,
            b.last_tok_idx)),
        *(() if impl == "xla" else map(jnp.asarray, b.tile_args)),
        block_size=BS, attn_impl=impl)


def _ragged_case(prefill_impl):
    """Slot 0 continues a prompt with 6 tokens (crossing a block edge),
    slot 1 decodes one (the one-row tile), slot 2 starts a 9-token prompt
    (two atoms)."""
    model, params = _model()
    kv = _pool(model.config, NUM_BLOCKS * BS)
    rng = np.random.RandomState(3)
    chunks = [(SequenceDescriptor(uid=i, pending=list(rng.randint(1, 500, n)),
                                  n_cached=int(CACHED[i]),
                                  blocks=list(TABLES[i])), n)
              for i, n in enumerate((6, 1, 9))]
    b = build_ragged_batch(chunks, T, S, BPS, atom_q=BQ)
    logits, new = _ragged_forward(model, params, kv, b, prefill_impl)
    written = np.concatenate([_slots(0, range(11, 17)), _slots(1, [9]),
                              _slots(2, range(9))])
    return kv, {"logits": logits}, new, written


CASES = {"decode_forward": (_decode_case, 0),
         "ragged_forward": (_ragged_case, 1)}


def _run(program, impl):
    case, which = CASES[program]
    kv, outs, new, written = case(IMPLS[impl][which])
    outs = {k: np.asarray(v) for k, v in outs.items()}
    outs["k_rows"] = np.asarray(new.k)[:, written]
    outs["v_rows"] = np.asarray(new.v)[:, written]
    return kv, outs, new, written


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("program", sorted(CASES))
def test_forward_returns_what_it_returned_before(program, impl):
    golden = np.load(GOLDEN)
    kv, outs, new, written = _run(program, impl)
    for name, got in outs.items():
        want = golden[f"{program}.{name}"]
        if got.dtype.kind in "iu":
            np.testing.assert_array_equal(got, want, err_msg=name)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4,
                                       err_msg=name)
    untouched = np.setdiff1d(np.arange(kv.num_slots), written)
    for before, after in ((kv.k, new.k), (kv.v, new.v)):
        assert after.shape == before.shape
        np.testing.assert_array_equal(np.asarray(after)[:, untouched],
                                      np.asarray(before)[:, untouched])


# ------------------------------------------------- a mixed round's two tiles
# six slots: three decode at different context lengths, one continues a
# chunked prompt with 13 tokens (two atoms of 8), one is a one-token prompt,
# one is idle. (cached, scheduled) by slot:
MIXED = [(11, 1), (9, 1), (25, 1), (8, 13), (0, 1)]
MIX_S, MIX_T = 6, 32
MIX_TABLES = np.random.RandomState(5).permutation(MIX_S * BPS).reshape(
    MIX_S, BPS).astype(np.int32)
MIX_ARCHS = {"plain": {}, "alibi": {"pos_embed": "alibi"},
             "window": {"sliding_window": 6}}


def _mixed_batch():
    rng = np.random.RandomState(9)
    chunks = [(SequenceDescriptor(uid=i, pending=list(rng.randint(1, 500, n)),
                                  n_cached=c, blocks=list(MIX_TABLES[i])), n)
              for i, (c, n) in enumerate(MIXED)]
    return build_ragged_batch(chunks, MIX_T, MIX_S, BPS, atom_q=BQ)


@pytest.mark.parametrize("arch", sorted(MIX_ARCHS))
def test_mixed_round_attends_row_for_row_as_the_xla_path(arch):
    """The attention of one layer, per packed row: atoms for the prompt
    chunk, the one-row tile for the four one-token chunks, against the XLA
    path that gathers every token's context."""
    model, _params = _model(**MIX_ARCHS[arch])
    cfg = model.config
    kv = _pool(cfg, (MIX_S * BPS + 1) * BS)
    b = _mixed_batch()
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (MIX_T, cfg.num_heads, cfg.head_dim), jnp.float32)
    alibi, window = M._arch_bias(cfg), M.AttnKind.of(cfg, 0).window
    ctx = M.PrefillAttnContext(
        k_cache=kv.k, v_cache=kv.v, layer=jnp.int32(1),
        token_seq=jnp.asarray(b.token_seq), token_pos=jnp.asarray(b.token_pos),
        block_tables=jnp.asarray(b.block_tables), block_size=BS, alibi=alibi,
        window=window, **{f: jnp.asarray(getattr(b, f)) for f in (
            "atom_qidx", "atom_pos0", "atom_qlen", "atom_tables", "atom_inv",
            "dec_row", "dec_len")})
    got = np.asarray(M._prefill_kernel_interpret_impl(q, ctx))
    want = np.asarray(M._prefill_xla_impl(q, ctx))
    n = b.current_tokens
    np.testing.assert_allclose(got[:n], want[:n], rtol=2e-5, atol=2e-5)
    assert not got[n:].any()           # padding rows: the dead atom's zeros


@pytest.mark.parametrize("arch", sorted(MIX_ARCHS))
def test_mixed_round_logits_match_xla_and_decode_forward(arch):
    """The whole forward: every slot's logits equal the XLA path's, and a
    one-token chunk's equal what ``decode_forward`` gives for the same
    pool — the same one-row tile over the same blocks."""
    model, params = _model(**MIX_ARCHS[arch])
    kv = _pool(model.config, (MIX_S * BPS + 1) * BS)
    b = _mixed_batch()
    got, new = _ragged_forward(model, params, kv, b, "kernel_interpret")
    want, new_xla = _ragged_forward(model, params, kv, b, "xla")
    live = len(MIXED)
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new.k), np.asarray(new_xla.k),
                               rtol=2e-4, atol=2e-4)
    one = np.asarray([n == 1 for _c, n in MIXED] + [False])
    tokens = np.where(one, b.tokens[b.last_tok_idx], 0)
    cached = np.asarray([c for c, _n in MIXED] + [0], np.int32)
    dec, _ = M.decode_forward(
        model, params, kv, jnp.asarray(tokens, jnp.int32), jnp.asarray(cached),
        jnp.asarray(b.block_tables), jnp.asarray(one), block_size=BS,
        attn_impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got)[one], np.asarray(dec)[one],
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- structure
def _compiled(program):
    """The jitted program (pool donated, as the engine builds it) lowered
    with a pool of 128 MiB a layer (K + V) beside a model and a batch of
    well under 1 MiB."""
    model, params = _model(num_layers=2)
    cfg = model.config
    slots = 1024 * BS
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, slots, cfg.num_kv_heads, 1024), jnp.float32)
    kv = BlockedKV(pool, pool)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if program == "decode_forward":
        fn = M.build_decode_forward_fn(model, BS, "xla")
        args = (i32(S), i32(S), i32(S, BPS),
                jax.ShapeDtypeStruct((S,), jnp.bool_))
    else:
        fn = M.build_ragged_forward_fn(model, BS, "xla")
        args = (i32(T), i32(T), i32(T), i32(S, BPS), i32(S))
    layer_kv = 2 * slots * cfg.num_kv_heads * 1024 * 4
    return fn.lower(params, kv, *args).compile(), layer_kv, cfg.num_layers


@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_forward_neither_copies_nor_doubles_the_pool(program):
    """Would have caught the per-layer slice, copy and write-back: as the
    scan's xs/ys the pool was held twice and each layer's K and V copied."""
    compiled, layer_kv, num_layers = _compiled(program)
    mem = compiled.memory_analysis()
    pool = num_layers * layer_kv
    assert mem.temp_size_in_bytes < layer_kv, (
        f"{program} holds {mem.temp_size_in_bytes} B of temporaries: one "
        f"layer's K + V is {layer_kv} B, so something copies a layer")
    assert mem.alias_size_in_bytes >= pool, "the donated pool is not reused"
    assert (mem.output_size_in_bytes - mem.alias_size_in_bytes
            + mem.temp_size_in_bytes) < 0.5 * pool


if __name__ == "__main__":   # freeze the case from the code on PYTHONPATH
    out = {}
    for program in CASES:
        for name, val in _run(program, "xla")[1].items():
            out[f"{program}.{name}"] = val
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    np.savez(GOLDEN, **out)
    print({k: v.shape for k, v in out.items()})
