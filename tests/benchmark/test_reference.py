"""The plain reference against the program's own model on seeded noise, at a
tiny size on the CPU (on the chip the harness compares them at the published
widths, outside the window)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference

from . import tiny

# mixtral is the family the temporary directory adds from files alone
CASES = {"phi": tiny.TINY_PHI,
         "mistral": {**tiny.TINY_MISTRAL, "sliding_window": 8},
         "mixtral": tiny.TINY_MIXTRAL}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def build(cfg):
    from deepspeedsyclsupport_tpu.models import build_model

    overrides = {**cfg["overrides"], "attn_impl": "xla", "dtype": "float32"}
    if cfg.get("sliding_window"):
        overrides["sliding_window"] = cfg["sliding_window"]
    if cfg.get("num_local_experts"):
        # the training forward drops what overflows an expert's capacity;
        # serving never does, nor the reference: room for every token
        overrides["capacity_factor"] = 4.0
    model = build_model(cfg["preset"], **overrides)
    model.seed = 3
    params = model.init_params()
    # biases and norm offsets start at zero: move every leaf off its init
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in
        zip(leaves, keys)])
    return model, params


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_logits_match_the_programs_model(bench, name):
    cfg = CASES[name]
    model, params = build(cfg)
    family = bench.family(cfg)
    arch = family.arch(cfg)
    assert family.program_widths(cfg) == {
        k: getattr(model.config, k) for k in family.program_widths(cfg)}
    ids = np.random.default_rng(0).integers(0, 512, (2, 24)).astype(np.int32)
    want = model.apply(params, jnp.asarray(ids))
    got = jnp.stack([family.sequence_logits(arch, params, jnp.asarray(r))
                     for r in ids])
    # float32 both sides; only the order of summation differs
    assert float(jnp.abs(want - got).max()) < 1e-4


@pytest.mark.parametrize("name", ["mistral", "phi"])
def test_reference_loss_matches_the_programs_loss(bench, name):
    cfg = CASES[name]
    model, params = build(cfg)
    ids = np.random.default_rng(1).integers(0, 512, (3, 16)).astype(np.int32)
    want = float(model.loss(params, {"input_ids": jnp.asarray(ids)}, None,
                            train=False)[0])
    got = reference.lm_loss(bench.family(cfg), cfg, params, ids)
    assert got == pytest.approx(want, rel=1e-5)


def test_greedy_margins_are_zero_for_the_argmax_and_large_for_a_wrong_token(
        bench):
    cfg = CASES["phi"]
    _model, params = build(cfg)
    family = bench.family(cfg)
    prompt = [5, 9, 200, 41]
    logits = family.sequence_logits(family.arch(cfg), params,
                                    jnp.asarray(prompt, jnp.int32))
    best = int(jnp.argmax(logits[-1]))
    worst = int(jnp.argmin(logits[-1]))
    assert reference.greedy_margins(family, cfg, params, prompt,
                                    [best]) == [0.0]
    assert reference.greedy_margins(family, cfg, params, prompt,
                                    [worst])[0] > 1.0


def test_a_family_without_a_file_is_refused_and_says_where_one_goes(bench):
    with pytest.raises(FileNotFoundError, match="families/mamba"):
        bench.family({**tiny.TINY_PHI, "model_type": "mamba"})


def test_a_family_file_that_lacks_a_function_is_refused(tmp_path):
    other = tiny.make_root(tmp_path)
    (tmp_path / "extra" / "families" / "half.py").write_text(
        "def arch(hf):\n    return {}\n")
    with pytest.raises(AttributeError, match="sequence_logits"):
        other.family({"model_type": "half"})
