"""The serving engine holds the projections a forward would re-lay as their
products read them (``model.serving_layout``): q, k and v, latent attention's
``w_qb`` and a sparse-attention indexer's ``w_qi`` ``[out, in]``, latent
attention's ``w_kvb`` as its two parts, head-major (``w_uk``, ``w_uv``); and
shows the world the model's public ``[in, out]`` tree.

That the engine's LOGITS are the parent's for a dense, a two-kind, a looped,
a patterned, a side-by-side, a latent and an indexed latent model is what the
family harness's greedy and plain references already hold every engine to
(``tests/family_harness.py``, ``tests/unit/greedy.py``: the references read
``model.init_params``' own tree, the engine re-lays it); those cases are not
repeated here. Here: the pair of functions itself, what ``engine.params``
gives back and takes, a snapshot, a tensor-parallel mesh, a quantized layer,
and the set-up span's counter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeedsyclsupport_tpu as ds
from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
from deepspeedsyclsupport_tpu.compression.quantize import (QuantTensor,
                                                           dequantize_tree,
                                                           quantize_tree)
from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.monitor import telemetry as tel

PROMPTS = [[7, 3, 11], [4, 100, 42, 8, 19]]
# latent attention at tiny widths: one leading dense layer and one expert
# layer (a stack each), eight heads of 16 un-rotated + 8 rotated dims over a
# query latent of 32 and a KV latent of 32, values 16 wide; every re-laid
# leaf is 4,096 elements or more a layer, so that ZeRO-Inference takes it
LATENT = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
              num_layers=2, first_k_dense_replace=1, num_heads=8,
              num_kv_heads=8, head_dim=24, vocab_size=128, q_lora_rank=32,
              kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, num_experts=4, num_experts_per_tok=2,
              max_seq_len=128, dtype="float32")
# ... and with an indexer whose queries read the query latent
INDEXER = dict(index_topk=8, index_heads=4, index_head_dim=32,
               index_rope_dim=8, index_q_latent=True)
MODELS = {
    # hidden 64, q 64, k and v 32 wide: ``wk`` and ``wv`` are not square, so
    # a leaf read the wrong way round does not trace
    "dense": lambda: build_model("tiny", dtype="float32"),
    "latent": lambda: build_model("glm-5", **LATENT, index_topk=0,
                                  index_q_latent=False),
    "indexed": lambda: build_model("glm-5", **LATENT, **INDEXER),
}
# what the engine lays out anew of an attention block, by the model: (the new
# leaves a stack, the public leaves they come of: ``w_kvb`` gives two)
RELAID = {"dense": (3, ("wq", "wk", "wv")),
          "latent": (3, ("w_qb", "w_kvb")),
          "indexed": (4, ("w_qb", "w_kvb", "w_qi"))}
H, R, NOPE, V = 8, 32, 16, 16


@pytest.fixture(scope="module")
def models():
    """name -> (model, its public tree), built when first asked."""
    built = {}

    def get(name):
        if name not in built:
            model = MODELS[name]()
            built[name] = model, jax.jit(model.init_params)(
                jax.random.PRNGKey(2))
        return built[name]
    return get


@pytest.fixture(scope="module")
def tiny(models):
    return models("dense")


def _engine(model, params, **kw):
    # (an indexer's pool holds two slots a row: an even block; a latent
    # model's attention through the exact XLA twins)
    return InferenceEngineV2(model, params, dtype=jnp.float32, block_size=8,
                             max_context=64, max_tokens_per_batch=16,
                             max_sequences=4, prefill_attn="xla",
                             decode_attn="xla", **kw)


def _same(a, b):
    return jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b) \
        and jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda x, y: x.shape == y.shape and x.dtype == y.dtype
            and bool(jnp.all(x == y)), a, b))


def _stacks(params):
    """The stacked layer trees of a public or a serving tree."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


# ------------------------------------------------------------ the two functions
def test_the_turn_is_by_the_leafs_name_and_its_own_inverse(tiny):
    """``wq``, ``wk`` and ``wv`` turn, wherever they stand (an attention
    block's, a lightning layer's) and however the leaf is stacked; nothing
    else does, and a tree WITHOUT the leaves comes back leaf for leaf; twice
    is the tree as it was."""
    model, params = tiny
    sala = build_model(
        "minicpm-sala", hidden_size=32, intermediate_size=48, num_layers=4,
        layer_pattern="*FLF", num_heads=4, num_kv_heads=2, head_dim=8,
        vocab_size=64, lightning_heads=2, lightning_head_dim=8,
        max_seq_len=128, dtype="float32")
    for cfg, tree in ((model.config, params),
                      (sala.config, jax.eval_shape(sala.init_params))):
        turned = M.serving_layout(tree, cfg)
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
    without = {"embed": params["embed"], "mlp": params["layers"]["mlp"]}
    for new, old in zip(jax.tree_util.tree_leaves(
            M.serving_layout(without, model.config)),
            jax.tree_util.tree_leaves(without)):
        assert new is old
    wk = params["layers"]["attn"]["wk"]
    assert wk.shape == (2, 64, 32)
    np.testing.assert_array_equal(
        np.asarray(M.serving_layout(params, model.config)
                   ["layers"]["attn"]["wk"]),
        np.swapaxes(np.asarray(wk), 1, 2))
    assert _same(M.public_layout(M.serving_layout(params, model.config)),
                 params)


@pytest.mark.parametrize("name", ["latent", "indexed"])
def test_the_latent_leaves_lie_as_their_products_read_them(models, name):
    """``w_qb`` and ``w_qi`` ``[L, out, in]``; ``w_kvb`` [L, r, h x (nope +
    v)] gone from the serving tree, in its place ``w_uk`` [L, h, r, nope]
    (each head's up-projection of keys, contracted over ``nope`` under the
    queries) and ``w_uv`` [L, h, v, r] (of values, contracted over the
    latent); in both stacks; every other leaf is the very object given."""
    model, params = models(name)
    served = M.serving_layout(params, model.config)
    for was, now in zip(_stacks(params), _stacks(served)):
        was, now = was["attn"], now["attn"]
        layers = was["w_kvb"].shape[0]
        assert "w_kvb" not in now
        assert set(now) - set(was) == {"w_uk", "w_uv"}
        assert now["w_qb"].shape == (layers, H * (NOPE + 8), 32)
        assert now["w_uk"].shape == (layers, H, R, NOPE)
        assert now["w_uv"].shape == (layers, H, V, R)
        by_head = np.asarray(was["w_kvb"]).reshape(layers, R, H, NOPE + V)
        np.testing.assert_array_equal(
            np.asarray(now["w_uk"]),
            by_head[..., :NOPE].transpose(0, 2, 1, 3))
        np.testing.assert_array_equal(
            np.asarray(now["w_uv"]),
            by_head[..., NOPE:].transpose(0, 2, 3, 1))
        np.testing.assert_array_equal(
            np.asarray(now["w_qb"]), np.swapaxes(np.asarray(was["w_qb"]),
                                                 1, 2))
        assert ("w_qi" in now) == (name == "indexed")
        if name == "indexed":
            assert now["w_qi"].shape == (layers, 4 * 32, 32)
        for leaf in set(was) - {"w_qb", "w_kvb", "w_qi"}:
            assert jax.tree_util.tree_leaves(now[leaf])[0] \
                is jax.tree_util.tree_leaves(was[leaf])[0], leaf
    assert served["embed"]["embedding"] is params["embed"]["embedding"]
    assert served["layers"]["moe"]["w_up"] is params["layers"]["moe"]["w_up"]


@pytest.mark.parametrize("on", ["arrays", "traced", "shapes"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_public_tree_comes_back_bit_for_bit(models, name, on):
    """``public_layout(serving_layout(t)) == t``: on arrays, on traced
    values (inside a ``jit``) and on ``ShapeDtypeStruct``s (structure,
    shapes and dtypes: what a compile for a described chip is handed)."""
    model, params = models(name)
    both = lambda t: M.public_layout(  # noqa: E731
        M.serving_layout(t, model.config))
    if on == "arrays":
        assert _same(both(params), params)
    elif on == "traced":
        assert _same(jax.jit(both)(params), params)
    else:
        shapes = jax.eval_shape(lambda: params)
        back = both(shapes)
        assert jax.tree_util.tree_structure(back) \
            == jax.tree_util.tree_structure(shapes)
        assert jax.tree_util.tree_leaves(back) \
            == jax.tree_util.tree_leaves(shapes)


def test_a_shapes_sharding_turns_with_it(tiny):
    """On ``ShapeDtypeStruct``s (what a compile for a described chip is
    handed) the sharding's last two axes swap with the shape's."""
    reset_world_topology()
    topo = ds.build_topology(dp=-1, tp=2)
    leaf = jax.ShapeDtypeStruct((3, 64, 32), jnp.bfloat16,
                                sharding=topo.sharding(None, None, "model"))
    out = M.serving_layout({"attn": {"wk": leaf, "wo": leaf}},
                           tiny[0].config)["attn"]
    assert out["wo"] is leaf
    assert (out["wk"].shape, out["wk"].dtype) == ((3, 32, 64), jnp.bfloat16)
    assert out["wk"].sharding == NamedSharding(topo.mesh,
                                               P(None, "model", None))
    reset_world_topology()


def test_the_parts_keep_the_whole_leafs_sharding(models):
    """``w_kvb`` [L, r, h x (nope + v)] over ``model`` on its out axis (a
    head's columns lie together: whole heads a device): both parts lie over
    ``model`` on their HEAD axis, the latent's axis follows the latent, and
    the joined leaf has the sharding it came with; on shapes and on
    arrays."""
    model, params = models("latent")
    reset_world_topology()
    topo = ds.build_topology(dp=-1, tp=2)
    whole = topo.sharding(None, "fsdp", "model")
    for leaf in (jax.ShapeDtypeStruct((2, R, H * (NOPE + V)), jnp.bfloat16,
                                      sharding=whole),
                 jax.device_put(params["layers"]["attn"]["w_kvb"], whole)):
        attn = M.serving_layout({"attn": {"w_kvb": leaf}},
                                model.config)["attn"]
        assert attn["w_uk"].sharding == NamedSharding(
            topo.mesh, P(None, "model", "fsdp", None))
        assert attn["w_uv"].sharding == NamedSharding(
            topo.mesh, P(None, "model", None, "fsdp"))
        back = M.public_layout({"attn": attn})["attn"]["w_kvb"]
        assert (back.shape, back.dtype) == (leaf.shape, leaf.dtype)
        assert back.sharding == whole
    assert bool(jnp.all(back == params["layers"]["attn"]["w_kvb"]))
    reset_world_topology()


# ------------------------------------------------------------------- the engine
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_engine_gives_back_the_tree_it_was_given(models, name):
    """``engine.params`` is the public tree, bit for bit; the leaves the
    engine did not re-lay are its OWN arrays, the ones it did are built when
    asked and not kept (one copy of each weight); a second engine built on
    it serves the same tokens (the dense model's: the latent ones serve
    through the setter's and the snapshot's cases); and the ``params`` span
    counts what was
    re-laid: the new leaves of every stack and their bytes, which are the
    public leaves' (a split moves every byte once)."""
    model, params = models(name)
    tel.setup_ledger_store.reset()
    eng = _engine(model, params)
    span = next(r for r in tel.setup_ledger()
                if r["kind"] == "span" and r["name"] == "params")
    leaves, public = RELAID[name]
    assert span["fields"] == {
        "relaid_leaves": leaves * len(_stacks(params)),
        "relaid_bytes": sum(stack["attn"][w].nbytes
                            for stack in _stacks(params) for w in public)}
    got = eng.params
    assert _same(got, params)
    own = eng._params
    assert got["embed"]["embedding"] is own["embed"]["embedding"]
    assert got["layers"]["attn"]["wo"] is own["layers"]["attn"]["wo"]
    assert eng.params["layers"]["attn"][public[1]] \
        is not got["layers"]["attn"][public[1]]
    held = [v for v in vars(eng).values() if isinstance(v, dict)
            and "layers" in v]
    assert held == [own]            # no public tree beside the engine's own
    if name == "dense":   # (the latent trees: bit for bit above, served below)
        want = eng.generate(PROMPTS, max_new_tokens=6)
        assert _engine(model, got).generate(PROMPTS, max_new_tokens=6) == want


@pytest.mark.parametrize("name", ["dense", "indexed"])
def test_an_assignment_is_re_laid(models, name):
    """``engine.params = tree`` takes the public layout (a planted fault of
    ``tools/h1_faults.py``; the hybrid engine hands its weights to the v1
    engine, which reads ``[in, out]`` as ever): the engine then serves what
    a fresh engine built on that tree serves."""
    model, params = models(name)
    fresh = _engine(model, jax.jit(model.init_params)(jax.random.PRNGKey(5)))
    other = fresh.params           # placed on the mesh, as the setter takes it
    eng = _engine(model, params)
    first = eng.generate(PROMPTS, max_new_tokens=6)
    eng.params = other
    attn = eng._params["layers"]["attn"]
    if name == "dense":
        assert attn["wv"].shape == (2, 32, 64)
    else:
        assert attn["w_uv"].shape == (1, H, V, R) and "w_kvb" not in attn
    assert _same(eng.params, other)
    again = eng.generate(PROMPTS, max_new_tokens=6)
    assert again == fresh.generate(PROMPTS, max_new_tokens=6) != first


@pytest.mark.parametrize("name", ["dense", "latent"])
def test_a_snapshot_holds_the_public_layout(models, name, tmp_path):
    """``serialize`` writes ``[in, out]`` and ``w_kvb`` whole: what
    ``deserialize`` loads against ``model.init_params``' shapes, so a
    snapshot of the parent loads here and one written here loads there."""
    from deepspeedsyclsupport_tpu.checkpoint.engine import load_tree

    model, params = models(name)
    eng = _engine(model, params)
    eng.serialize(str(tmp_path / "snap"))
    own = eng.params
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, own)
    state, _ = load_tree(str(tmp_path / "snap"), {"params": (
        jax.eval_shape(model.init_params), shardings)})
    assert _same(state["params"], params)
    loaded = InferenceEngineV2.deserialize(str(tmp_path / "snap"))
    attn = loaded._params["layers"]["attn"]
    if name == "dense":
        assert attn["wk"].shape == (2, 32, 64)
    else:
        assert attn["w_uk"].shape == (1, H, R, NOPE)
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
    for name in M.QKV:
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
    model, params = tiny
    eng = _engine(model, params, quantize_weights=True)
    attn = eng._params["layers"]["attn"]
    assert isinstance(attn["wq"], QuantTensor)
    assert attn["wq"].shape == (2, 64, 64) and attn["wk"].shape == (2, 32, 64)
    assert isinstance(eng.params["layers"]["attn"]["wq"], QuantTensor)
    quantized = {**params, "layers": quantize_tree(
        params["layers"], eng.config.quant_group_size, stacked=True,
        bits=eng.config.quant_bits)}
    assert M.serving_layout(quantized, model.config)["layers"]["attn"]["wq"] \
        is quantized["layers"]["attn"]["wq"]
    plain = _engine(model, dequantize_tree(quantized, jnp.float32))
    prompt = [1, 5, 9, 200, 3]
    np.testing.assert_allclose(
        np.asarray(eng.put([1], [prompt])[1]),
        np.asarray(plain.put([1], [prompt])[1]), rtol=1e-4, atol=1e-4)


def test_a_quantized_latent_layer_keeps_its_public_form(models):
    """The expert stack's ``w_qb``, ``w_kvb`` and ``w_qi`` as
    ``QuantTensor``s stay in the serving tree under their public names and
    shapes (``w_kvb`` WHOLE: no parts) beside the dense stack's plain leaves,
    which are re-laid; ``model._dequant`` lays out the layer it materialises
    (the parts come of it there), and the engine serves what a plain engine
    serves on the de-quantized weights."""
    model, params = models("indexed")
    eng = _engine(model, params, quantize_weights=True)
    attn = eng._params["layers"]["attn"]
    for leaf, shape in (("w_qb", (1, 32, H * 24)),
                        ("w_kvb", (1, R, H * (NOPE + V))),
                        ("w_qi", (1, 32, 4 * 32))):
        assert isinstance(attn[leaf], QuantTensor) \
            and attn[leaf].shape == shape, leaf
    assert "w_uk" not in attn
    assert eng._params["dense_layers"]["attn"]["w_uk"].shape == (1, H, R, NOPE)
    assert isinstance(eng.params["layers"]["attn"]["w_kvb"], QuantTensor)
    layer = M._dequant(jax.tree_util.tree_map(lambda x: x[0], attn),
                       jnp.float32, model.config)
    assert layer["w_uk"].shape == (H, R, NOPE) and "w_kvb" not in layer
    assert layer["w_qb"].shape == (H * 24, 32)
    quantized = {**params, "layers": quantize_tree(
        params["layers"], eng.config.quant_group_size, stacked=True,
        bits=eng.config.quant_bits)}
    plain = _engine(model, dequantize_tree(quantized, jnp.float32))
    prompt = [1, 5, 9, 100, 3]
    np.testing.assert_allclose(
        np.asarray(eng.put([1], [prompt])[1]),
        np.asarray(plain.put([1], [prompt])[1]), rtol=1e-4, atol=1e-4)
