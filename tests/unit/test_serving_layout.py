"""The serving engine holds its q, k and v projection weights ``[out, in]``,
as their products read them (``model.serving_layout``), and shows the world
the model's public ``[in, out]`` tree.

That the engine's LOGITS are the parent's for a dense, a two-kind, a looped,
a patterned and a side-by-side model is what the family harness's greedy and
plain references already hold every engine to (``tests/family_harness.py``,
``tests/unit/greedy.py``: the references read ``model.init_params``' own
tree, the engine re-lays it); those cases are not repeated here. Here: the
pair of functions itself, what ``engine.params`` gives back and takes, a
snapshot, a tensor-parallel mesh, a quantized layer, and the set-up span's
counter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeedsyclsupport_tpu as ds
from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
from deepspeedsyclsupport_tpu.compression.quantize import (QuantTensor,
                                                           quantize_tree)
from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.monitor import telemetry as tel

PROMPTS = [[7, 3, 11], [4, 100, 42, 8, 19]]


@pytest.fixture(scope="module")
def tiny():
    """hidden 64, q 64, k and v 32 wide: ``wk`` and ``wv`` are not square,
    so a leaf read the wrong way round does not trace."""
    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


def _engine(model, params, **kw):
    return InferenceEngineV2(model, params, dtype=jnp.float32, block_size=8,
                             max_context=64, max_tokens_per_batch=16,
                             max_sequences=4, **kw)


def _same(a, b):
    return jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: x.shape == y.shape and bool(jnp.all(x == y)), a, b))


# ------------------------------------------------------------ the two functions
def test_the_turn_is_by_the_leafs_name_and_its_own_inverse(tiny):
    """``wq``, ``wk`` and ``wv`` turn, wherever they stand (an attention
    block's, a lightning layer's) and however the leaf is stacked; nothing
    else does; twice is the tree as it was."""
    _model, params = tiny
    sala = build_model(
        "minicpm-sala", hidden_size=32, intermediate_size=48, num_layers=4,
        layer_pattern="*FLF", num_heads=4, num_kv_heads=2, head_dim=8,
        vocab_size=64, lightning_heads=2, lightning_head_dim=8,
        max_seq_len=128, dtype="float32")
    for tree in (params, jax.eval_shape(sala.init_params)):
        turned = M.serving_layout(tree)
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        for (path, was), now in zip(flat, jax.tree_util.tree_leaves(turned)):
            name = path[-1].key
            if name in ("wq", "wk", "wv"):
                assert now.shape == (*was.shape[:-2], *was.shape[:-3:-1])
            else:
                assert now is was, name
        back = M.public_layout(turned)
        assert jax.tree_util.tree_structure(back) \
            == jax.tree_util.tree_structure(tree)
        assert [x.shape for x in jax.tree_util.tree_leaves(back)] \
            == [x.shape for x in jax.tree_util.tree_leaves(tree)]
    wk = params["layers"]["attn"]["wk"]
    assert wk.shape == (2, 64, 32)
    np.testing.assert_array_equal(
        np.asarray(M.serving_layout(params)["layers"]["attn"]["wk"]),
        np.swapaxes(np.asarray(wk), 1, 2))
    assert _same(M.public_layout(M.serving_layout(params)), params)


def test_a_shapes_sharding_turns_with_it():
    """On ``ShapeDtypeStruct``s (what a compile for a described chip is
    handed) the sharding's last two axes swap with the shape's."""
    reset_world_topology()
    topo = ds.build_topology(dp=-1, tp=2)
    leaf = jax.ShapeDtypeStruct((3, 64, 32), jnp.bfloat16,
                                sharding=topo.sharding(None, None, "model"))
    out = M.serving_layout({"attn": {"wk": leaf, "wo": leaf}})["attn"]
    assert out["wo"] is leaf
    assert (out["wk"].shape, out["wk"].dtype) == ((3, 32, 64), jnp.bfloat16)
    assert out["wk"].sharding == NamedSharding(topo.mesh,
                                               P(None, "model", None))
    reset_world_topology()


# ------------------------------------------------------------------- the engine
def test_the_engine_gives_back_the_tree_it_was_given(tiny):
    """``engine.params`` is the public tree, bit for bit; the leaves the
    engine did not re-lay are its OWN arrays, the ones it did are built when
    asked and not kept (one copy of each weight); a second engine built on
    it serves the same tokens; and the ``params`` span counts what was
    re-laid."""
    model, params = tiny
    tel.setup_ledger_store.reset()
    eng = _engine(model, params)
    span = next(r for r in tel.setup_ledger()
                if r["kind"] == "span" and r["name"] == "params")
    attn = params["layers"]["attn"]
    assert span["fields"] == {
        "relaid_leaves": 3,
        "relaid_bytes": sum(attn[w].nbytes for w in M.TURNED)}
    got = eng.params
    assert _same(got, params)
    own = eng._params
    assert got["embed"]["embedding"] is own["embed"]["embedding"]
    assert got["layers"]["attn"]["wo"] is own["layers"]["attn"]["wo"]
    assert own["layers"]["attn"]["wk"].shape == (2, 32, 64)
    assert eng.params["layers"]["attn"]["wk"] \
        is not got["layers"]["attn"]["wk"]
    held = [v for v in vars(eng).values() if isinstance(v, dict)
            and "layers" in v]
    assert held == [own]            # no public tree beside the engine's own
    want = eng.generate(PROMPTS, max_new_tokens=6)
    assert _engine(model, got).generate(PROMPTS, max_new_tokens=6) == want


def test_an_assignment_is_re_laid(tiny):
    """``engine.params = tree`` takes the public layout (a planted fault of
    ``tools/h1_faults.py``; the hybrid engine hands its weights to the v1
    engine, which reads ``[in, out]`` as ever): the engine then serves what
    a fresh engine built on that tree serves."""
    model, params = tiny
    fresh = _engine(model, model.init_params(jax.random.PRNGKey(5)))
    other = fresh.params           # placed on the mesh, as the setter takes it
    eng = _engine(model, params)
    first = eng.generate(PROMPTS, max_new_tokens=6)
    eng.params = other
    assert eng._params["layers"]["attn"]["wv"].shape == (2, 32, 64)
    assert _same(eng.params, other)
    again = eng.generate(PROMPTS, max_new_tokens=6)
    assert again == fresh.generate(PROMPTS, max_new_tokens=6) != first


def test_a_snapshot_holds_the_public_layout(tiny, tmp_path):
    """``serialize`` writes ``[in, out]``: what ``deserialize`` loads against
    ``model.init_params``' shapes, so a snapshot of the parent loads here
    and one written here loads there."""
    from deepspeedsyclsupport_tpu.checkpoint.engine import load_tree

    model, params = tiny
    eng = _engine(model, params)
    eng.serialize(str(tmp_path / "snap"))
    own = eng.params
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, own)
    state, _ = load_tree(str(tmp_path / "snap"), {"params": (
        jax.eval_shape(model.init_params), shardings)})
    assert _same(state["params"], params)
    loaded = InferenceEngineV2.deserialize(str(tmp_path / "snap"))
    assert loaded._params["layers"]["attn"]["wk"].shape == (2, 32, 64)
    assert loaded.generate(PROMPTS, max_new_tokens=6) \
        == eng.generate(PROMPTS, max_new_tokens=6)


def test_a_tensor_parallel_mesh_shards_the_re_laid_leaf_on_its_out_axis(tiny):
    """tp = 2 on the CPU mesh: the public ``wq`` ``[L, in, out]`` is placed
    ``(None, fsdp, model)``; re-laid ``[L, out, in]`` it lies over ``model``
    on its OUT axis still, and the logits are the one-device engine's."""
    model, params = tiny
    prompt = [1, 5, 9, 200, 3]
    reset_world_topology()
    base = np.asarray(_engine(model, params).put([1], [prompt])[1])
    reset_world_topology()
    topo = ds.build_topology(dp=-1, tp=2)
    eng = _engine(model, params, topology=topo)
    for name in M.TURNED:
        spec = tuple(eng._params["layers"]["attn"][name].sharding.spec)
        assert spec[1] == "model" and spec[2] != "model", (name, spec)
    assert tuple(eng.params["layers"]["attn"]["wq"].sharding.spec)[2] \
        == "model"
    np.testing.assert_allclose(np.asarray(eng.put([1], [prompt])[1]), base,
                               rtol=1e-5, atol=1e-5)
    reset_world_topology()


def test_a_quantized_layer_is_left_as_it_is(tiny):
    """ZeRO-Inference: a ``QuantTensor`` keeps its form, the public
    ``[in, out]`` (its groups lie along ``out`` as they did), and the layer
    is turned where it is materialised (``model._dequant``), beside the
    plain leaves the engine re-laid (``wk``, ``wv``: too small to quantize):
    the engine serves what a plain engine serves on the de-quantized
    weights."""
    from deepspeedsyclsupport_tpu.compression.quantize import dequantize_tree

    model, params = tiny
    eng = _engine(model, params, quantize_weights=True)
    attn = eng._params["layers"]["attn"]
    assert isinstance(attn["wq"], QuantTensor)
    assert attn["wq"].shape == (2, 64, 64) and attn["wk"].shape == (2, 32, 64)
    assert isinstance(eng.params["layers"]["attn"]["wq"], QuantTensor)
    quantized = {**params, "layers": quantize_tree(
        params["layers"], eng.config.quant_group_size, stacked=True,
        bits=eng.config.quant_bits)}
    assert M.serving_layout(quantized)["layers"]["attn"]["wq"] \
        is quantized["layers"]["attn"]["wq"]
    plain = _engine(model, dequantize_tree(quantized, jnp.float32))
    prompt = [1, 5, 9, 200, 3]
    np.testing.assert_allclose(
        np.asarray(eng.put([1], [prompt])[1]),
        np.asarray(plain.put([1], [prompt])[1]), rtol=1e-4, atol=1e-4)
