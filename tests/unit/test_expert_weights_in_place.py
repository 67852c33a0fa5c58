"""The serving forwards read the routed experts' weights in place (ISSUE 31).

``jax.lax.ragged_dot`` compiles to a custom call on the TPU, a custom call
takes whole buffers, so while the stacked expert leaves ``[L_moe, E, ., .]``
rode as the layer scan's xs XLA copied one layer's three matrices out of
them before every read (1.56 x the grouped GEMMs' own time in OLMoE's decode
step). Now ``_scan_layers`` closes over the stack and hands ``layer`` the
index within it, and ``moe_mlp_nodrop`` reads the layer's E groups of the
``[L_moe x E, ., .]`` view.

* frozen case: both forwards of an OLMoE-like and a Xing4-like model (leading
  dense layer, shared expert, sigmoid routing) return what the PARENT commit
  returned (``data/expert_forward_golden.npz``, written by running THIS file
  as a script against a checkout of the commit to freeze:
  ``PYTHONPATH=<checkout> python tests/unit/test_expert_weights_in_place.py``);
  the dense model's own frozen case is ``test_kv_pool_in_place.py``'s;
* the function: on the 4-D stack with ``layer`` static or traced, output and
  sizes equal the 3-D call on ``w[layer]``;
* the counters: ``MoeCounters.load`` stays ``[L_moe, E]``, each layer's row
  summing to ``k x live rows``.

What the compiler makes of it at OLMoE's widths (no materialised slice, no
temporaries the size of an expert matrix) is ``test_chip_compile.py``'s case.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.compression.quantize import quantize_tree
from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (BlockedKV,
                                                            MoeCounters)
from deepspeedsyclsupport_tpu.inference.v2.ragged import (SequenceDescriptor,
                                                          build_ragged_batch)
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "expert_forward_golden.npz")
BS, BPS, S, T = 8, 4, 3, 16     # block size, blocks/seq, slots, token budget
NUM_SLOTS = (S * BPS + 1) * BS
# three sequences over disjoint, out-of-order blocks: 11, 9 and 0 tokens cached
TABLES = np.asarray([[5, 2, 9, 0], [7, 11, 1, 3], [4, 6, 8, 10]], np.int32)
CACHED = np.asarray([11, 9, 0], np.int32)

MODELS = {
    # 4 expert layers, softmax top-3 not renormalised, QK-norm
    "olmoe": ("olmoe-1b-7b", dict(
        hidden_size=64, intermediate_size=32, num_layers=4, num_heads=4,
        num_kv_heads=4, head_dim=16, vocab_size=512, num_experts=8,
        num_experts_per_tok=3, max_seq_len=128, dtype="float32")),
    # 2 dense layers then 3 expert layers: the pool's index and the expert
    # stack's differ. Latent pool, 4 streams, sigmoid top-3 + a shared expert
    "xing4": ("xing4-29b-a4b", dict(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_layers=5, first_k_dense_replace=2, num_heads=4, num_kv_heads=4,
        head_dim=24, vocab_size=512, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=8, num_experts_per_tok=3, max_seq_len=256,
        dtype="float32")),
}


@pytest.fixture(scope="module", params=sorted(MODELS))
def built(request):
    return request.param, *_built(request.param)


@functools.lru_cache(maxsize=None)
def _built(name):
    """The model and seeded weights with EVERY leaf moved off its init (the
    Xing4 preset draws its routed experts at 1/E of the shared one's). Drawn
    in ONE program: a draw a leaf is a program a shape otherwise. In the
    layout the forwards take (``M.serving_layout``)."""
    preset, widths = MODELS[name]
    model = build_model(preset, **widths)

    def drawn():
        leaves, tree = jax.tree_util.tree_flatten(
            model.init_params(jax.random.PRNGKey(3)))
        keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            x + 0.1 * jax.random.normal(k, x.shape)
            for x, k in zip(leaves, keys)])
    return model, M.serving_layout(jax.jit(drawn)(), model.config)


def _pool(cfg, seed=0):
    """A pool of seeded noise with zeroed counters."""
    k, v = jax.random.split(jax.random.PRNGKey(seed))
    moe = MoeCounters(jnp.zeros((cfg.num_moe_layers, cfg.num_experts),
                                jnp.int32), jnp.int32(0))
    if cfg.kv_lora_rank:
        shape = (cfg.num_layers, NUM_SLOTS, cfg.latent_kv_dim)
        return BlockedKV(jax.random.normal(k, shape, jnp.float32), None, moe)
    shape = (cfg.num_layers, NUM_SLOTS, cfg.num_kv_heads, cfg.head_dim)
    return BlockedKV(jax.random.normal(k, shape, jnp.float32),
                     jax.random.normal(v, shape, jnp.float32), moe)


def _slots(seq, positions):
    positions = np.asarray(positions)
    return TABLES[seq, positions // BS] * BS + positions % BS


def _decode(model, params):
    """Slots 0 and 1 decode one token each, slot 2 is idle: 2 live rows."""
    kv = _pool(model.config)
    logits, new = M.decode_forward(
        model, params, kv, jnp.asarray([17, 230, 0], jnp.int32),
        jnp.asarray(CACHED), jnp.asarray(TABLES),
        jnp.asarray([True, True, False]), block_size=BS, attn_impl="xla")
    return logits[:2], new, np.concatenate([_slots(0, [11]), _slots(1, [9])])


def _ragged(model, params):
    """Slot 0 continues a prompt with 5 tokens, slot 1 decodes one, slot 2
    starts a 7-token prompt: 13 live rows of a budget of 16."""
    kv = _pool(model.config)
    rng = np.random.RandomState(3)
    chunks = [(SequenceDescriptor(uid=i, pending=list(rng.randint(1, 500, n)),
                                  n_cached=int(CACHED[i]),
                                  blocks=list(TABLES[i])), n)
              for i, n in enumerate((5, 1, 7))]
    b = build_ragged_batch(chunks, T, S, BPS, atom_q=8)
    logits, new = M.ragged_forward(
        model, params, kv, *(jnp.asarray(a) for a in (
            b.tokens, b.token_seq, b.token_pos, b.block_tables,
            b.last_tok_idx)), block_size=BS, attn_impl="xla")
    return logits, new, np.concatenate(
        [_slots(0, range(11, 16)), _slots(1, [9]), _slots(2, range(7))])


PROGRAMS = {"decode_forward": _decode, "ragged_forward": _ragged}


def _outputs(program, model, params):
    """What the forward returned, the pool as the rows it wrote (one a live
    token), and the number of live rows."""
    logits, new, written = PROGRAMS[program](model, params)
    return {"logits": np.asarray(logits),
            "k_rows": np.asarray(new.k)[:, written],
            "load": np.asarray(new.moe.load),
            "touched": np.asarray(new.moe.touched)}, len(written)


# ------------------------------------------------------------- frozen case
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_forward_returns_what_the_parent_returned(built, program):
    name, model, params = built
    golden = np.load(GOLDEN)
    outs, _live = _outputs(program, model, params)
    for key, got in outs.items():
        want = golden[f"{name}.{program}.{key}"]
        if got.dtype.kind in "iu":
            np.testing.assert_array_equal(got, want, err_msg=key)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                       err_msg=key)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_load_is_one_row_an_expert_layer_summing_to_k_live_rows(built,
                                                                program):
    _name, model, params = built
    cfg = model.config
    outs, live = _outputs(program, model, params)
    assert outs["load"].shape == (cfg.num_moe_layers, cfg.num_experts)
    assert outs["load"].sum(1).tolist() \
        == [cfg.num_experts_per_tok * live] * cfg.num_moe_layers
    assert outs["touched"] == (outs["load"] > 0).sum()
    # routing differs between layers: each row is its own layer's
    assert len({tuple(r) for r in outs["load"]}) > 1


@pytest.mark.parametrize("kind", ["quantised", "other_dtype"])
def test_leaves_that_keep_their_slice_give_the_same_logits(kind):
    """A ``QuantTensor`` expert leaf and one whose dtype is not the
    activations' stay in the scan's xs, sliced by layer as before."""
    model, params = _built("olmoe")
    moe = params["layers"]["moe"]
    names = ("w_gate", "w_up", "w_down")
    if kind == "quantised":
        changed = quantize_tree({n: moe[n] for n in names}, min_size=64,
                                stacked=True)
        exact = jax.tree_util.tree_map(
            lambda q: q.dequantize(jnp.float32), changed,
            is_leaf=lambda q: hasattr(q, "dequantize"))
    else:
        changed = {n: moe[n].astype(jnp.bfloat16) for n in names}
        exact = {n: changed[n].astype(jnp.float32) for n in names}

    def with_experts(leaves):
        return {**params, "layers": {**params["layers"],
                                     "moe": {**moe, **leaves}}}

    got, new, _ = _decode(model, with_experts(changed))
    want, ref, _ = _decode(model, with_experts(exact))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new.moe.load),
                                  np.asarray(ref.moe.load))


# ------------------------------------------------------------ the function
N_LAYERS, ROWS = 5, 12
LIVE = {
    "all": None,
    "pads": np.arange(ROWS) < 7,
    # one live row: k experts get a row, every other expert none
    "an_expert_with_no_row": np.arange(ROWS) == 4,
}


@pytest.fixture(scope="module")
def stack():
    """A model's config and a [5, E, ., .] stack of its expert leaves."""
    model = build_model(MODELS["olmoe"][0], **{**MODELS["olmoe"][1],
                                               "num_layers": N_LAYERS})
    moe = model.init_params(jax.random.PRNGKey(7))["layers"]["moe"]
    moe = {n: w + 0.1 * jax.random.normal(jax.random.PRNGKey(i), w.shape)
           for i, (n, w) in enumerate(sorted(moe.items()))}
    x = jax.random.normal(jax.random.PRNGKey(11),
                          (ROWS, model.config.hidden_size))
    return model.config, moe, x


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("layer", [0, N_LAYERS // 2, N_LAYERS - 1])
def test_stack_and_layer_equal_the_layers_own_leaves(stack, layer, live,
                                                     traced):
    cfg, moe, x = stack
    mask = None if LIVE[live] is None else jnp.asarray(LIVE[live])
    one = jax.tree_util.tree_map(lambda w: w[layer], moe)
    want, want_sizes = moe_mlp_nodrop(one, x, cfg, mask)
    # router (and nothing else of the experts' three) is sliced as the scan
    # slices it; the three matrices are the whole stack
    p = {**one, **{n: moe[n] for n in ("w_gate", "w_up", "w_down")}}
    if traced:
        got, sizes = jax.jit(
            lambda p, x, l: moe_mlp_nodrop(p, x, cfg, mask, layer=l))(
                p, x, jnp.int32(layer))
    else:
        got, sizes = moe_mlp_nodrop(p, x, cfg, mask, layer=layer)
    assert sizes.shape == (cfg.num_experts,)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(want_sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    n_live = ROWS if mask is None else int(LIVE[live].sum())
    assert int(sizes.sum()) == cfg.num_experts_per_tok * n_live
    if mask is not None:
        assert not np.asarray(got)[~LIVE[live]].any()
    if live == "an_expert_with_no_row":
        assert int((sizes == 0).sum()) \
            == cfg.num_experts - cfg.num_experts_per_tok


def test_a_stack_of_another_dtype_is_refused(stack):
    """``.astype`` on the stack would convert L x the bytes every layer."""
    cfg, moe, x = stack
    with pytest.raises(ValueError, match="dtype"):
        moe_mlp_nodrop(moe | {"router": moe["router"][0]},
                       x.astype(jnp.bfloat16), cfg, layer=1)


if __name__ == "__main__":   # freeze the case from the code on PYTHONPATH
    out = {}
    for name in sorted(MODELS):
        model, params = _built(name)
        for program in PROGRAMS:
            for key, val in _outputs(program, model, params)[0].items():
                out[f"{name}.{program}.{key}"] = val
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    np.savez(GOLDEN, **out)
    print({k: v.shape for k, v in out.items()})
