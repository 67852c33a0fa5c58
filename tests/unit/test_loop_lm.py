"""A looped stack on the serving path (``ModelConfig.total_ut_steps``:
the layers run several times over shared weights, ``model._scan_passes``):
the served logits against the plain float32 reference of the family
(``benchmark/families/ouro.py``: independent of the code under test), a
pass reading its OWN cache rows, the exit rule and its device counter, what
sees only blocks (eviction and requeue, the prefix cache) working unchanged,
the pool's shape, the refusals by name, and one pass without the extra norms
being today's program bit for bit.

Everything here is float32 on the CPU at tiny widths. The tolerance 2e-5
logit-std is ten times what the rounding of two float32 programs that sum
in different orders reads here (1-2e-6); a wrong cache row, a missing norm
or a wrong exit pass read 0.1 to several (the cases below say which)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity
from deepspeedsyclsupport_tpu.inference.v2 import (ServingPolicyConfig,
                                                   ServingSession)
from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats
from deepspeedsyclsupport_tpu.models import ModelConfig, build_model
from tests.family_harness import PAD, Harness, family  # noqa: F401
from tests.unit import stream_ends

TOL = 2e-5
L, V = 3, 512
HF = {"model_type": "ouro", "num_attention_heads": 4, "hidden_size": 64,
      "head_dim": 16, "intermediate_size": 96, "num_hidden_layers": L,
      "num_key_value_heads": 4, "vocab_size": V, "rope_theta": 1000000,
      "rms_norm_eps": 1e-6, "total_ut_steps": 4, "early_exit_threshold": 1}
ENGINE = {"block_size": 16, "max_context": 128, "max_tokens_per_batch": 24,
          "max_sequences": 4, "num_blocks": 16, "prefill_attn": "xla",
          "decode_attn": "xla"}


PROMPT = np.random.default_rng(0).integers(0, V, 50).tolist()
H = Harness(HF, ENGINE, [PROMPT])
engine_of = H.engine_of


def hf(passes, threshold=1.0):
    return {**HF, "total_ut_steps": passes, "early_exit_threshold": threshold}


@functools.lru_cache(maxsize=None)
def built(passes, threshold=1.0, seed=3):
    """The preset at the tiny widths, every leaf moved off its init (the
    norm scales are constants there and the gate's bias zero: a program
    that left one out would not show). One model and one tree a
    configuration: a case that changes a leaf builds a tree of its own
    around it."""
    model = build_model(
        "ouro-2.6b", hidden_size=64, intermediate_size=96, num_layers=L,
        num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=V,
        max_seq_len=256, total_ut_steps=passes,
        early_exit_threshold=threshold, dtype="float32")

    def drawn():      # ONE program: a draw a leaf is one a shape otherwise
        params = model.init_params(jax.random.PRNGKey(seed))
        leaves, tree = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
        moved = [x * (1.0 + 0.2 * jax.random.normal(k, x.shape))
                 if x.ndim > 1
                 else x + 0.2 * jnp.abs(x).mean()
                 * jax.random.normal(k, x.shape) + 0.05 * (x.shape == (1,))
                 for x, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(tree, moved)

    params = jax.jit(drawn)()
    if "exit_gate" in params:     # gates from ~0.05 to ~0.95, not all ~0.5
        params["exit_gate"]["kernel"] = params["exit_gate"]["kernel"] * 4.0
    return model, params


# ------------------------------------------------------- against the family
@pytest.mark.parametrize("attn", ["xla", "kernels"])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_served_logits_are_the_references(passes, attn):
    """The prompt in three chunks of 24, then six of its own greedy tokens
    one at a time through the pool: every row within ``TOL`` of the family's
    float32 forward of the whole sequence, through the XLA attention and
    through both paged kernels (interpreted)."""
    model, params = built(passes)
    kw = {} if attn == "xla" else {
        "prefill_attn": "kernel_interpret", "decode_attn": "pallas_interpret",
        "atom_q_size": 8}
    eng = engine_of(model, params, **kw)
    logits, tokens = parity.served_logits(eng, 1, PROMPT, 6)
    want = H.reference(params, PROMPT + tokens, hf(passes))[-7:]
    assert parity.row_errors(logits, want).max() < TOL
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


@pytest.mark.parametrize("passes", [2, 4])
def test_generate_continues_as_the_reference_does(passes):
    """Two prompts through ``generate()``, a decode step a token: each
    stream's greedy tokens are the reference's own greedy continuation."""
    model, params = built(passes)
    prompts = [PROMPT[:9], PROMPT[9:30]]
    got = engine_of(model, params).generate(prompts, max_new_tokens=7)
    for prompt, toks in zip(prompts, got):
        rows = H.reference(params, prompt + toks, hf(passes))
        assert rows[len(prompt) - 1:-1].argmax(-1).tolist() == list(toks)


# ------------------------------------------------ a pass reads its own rows
def _decode_once(passes, edit=None):
    """Prefill 20 tokens, then ONE ``decode_forward`` over a pool that
    ``edit(engine) -> (k, v)`` changed first; -> the step's logits [V]."""
    model, params = built(passes)
    eng = engine_of(model, params)
    eng.put([7], [PROMPT[:20]])
    if edit is not None:
        eng.kv = eng.kv._replace(**dict(zip("kv", edit(eng))))
    return np.asarray(eng.put([7], [[5]])[7])


def test_swapping_two_passes_rows_changes_the_logits():
    """The pool has ``passes x layers`` rows and pass ``u`` writes and reads
    rows ``u L .. u L + L - 1``. NaN in every slot of the blocks the
    sequence does NOT hold changes nothing (what no pass may read); the two
    passes' rows of the cached context swapped change the logits by 0.05
    logit-std and more: another pass's keys and values are another function
    of the context."""
    clean = _decode_once(2)

    def poison(eng):
        foreign = np.ones(eng.kv.k.shape[1], bool)
        bs = ENGINE["block_size"]
        for b in eng.seqs[7].blocks:
            foreign[b * bs:(b + 1) * bs] = False
        return tuple(jnp.where(foreign[None, :, None, None], jnp.nan, pool)
                     for pool in eng.kv.pools)

    assert np.array_equal(_decode_once(2, poison), clean)

    def swap(eng):
        order = np.r_[L:2 * L, 0:L]
        return eng.kv.k[order], eng.kv.v[order]

    swapped = _decode_once(2, swap)
    assert np.isfinite(swapped).all()
    assert np.abs(swapped - clean).max() / clean.std() > 0.05


@pytest.mark.parametrize("other", [1, 2, 3])
def test_poisoning_another_passes_rows_moves_only_that_pass(other):
    """Four passes, threshold 0 (every row exits after pass 0, but every
    pass is computed): the logits are pass 0's, which attends rows 0..L-1
    only: NaN written over pass ``other``'s rows of the cached context
    changes NOTHING of them (were a pass to read another's rows, NaN would
    reach the logits); over pass 0's own rows it does."""
    def run(poisoned):
        model, params = built(4, threshold=0.0)
        eng = engine_of(model, params)
        eng.put([7], [PROMPT[:20]])
        rows = np.zeros(4 * L, bool)
        if poisoned is not None:
            rows[poisoned * L:(poisoned + 1) * L] = True
        mask = rows[:, None, None, None]
        eng.kv = eng.kv._replace(k=jnp.where(mask, jnp.nan, eng.kv.k),
                                 v=jnp.where(mask, jnp.nan, eng.kv.v))
        return np.asarray(eng.put([7], [[5]])[7])

    clean = run(None)
    assert np.array_equal(run(other), clean)
    assert not np.isfinite(run(0)).all()


# ------------------------------------------------ one pass is today's program
def test_one_pass_without_the_extra_norms_is_the_program_that_stood():
    """``total_ut_steps`` 1 and no ``sandwich_norm`` trace no outer loop and
    no norm: the jaxpr of both forwards of ``tiny`` is, character for
    character, the one of a config object that never had the fields set,
    and carries neither scope; and the pool has no counter leaf."""
    model = build_model("tiny", dtype="float32")
    assert (model.config.total_ut_steps, model.config.sandwich_norm) \
        == (1, False)
    params = model.init_params()
    eng = engine_of(model, params)
    assert eng.kv.exit_pass is None and eng.round_tail() is None
    assert "exit_gate" not in params
    assert "attn_post_norm" not in params["layers"]
    s, t = ENGINE["max_sequences"], ENGINE["max_tokens_per_batch"]
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    dec = jax.make_jaxpr(lambda p, kv, *a: M.decode_forward(
        model, p, kv, *a, block_size=16, attn_impl="xla"))(
        eng._params, eng.kv, i32(s), i32(s), i32(s, 8), jnp.zeros((s,), bool))
    rag = jax.make_jaxpr(lambda p, kv, *a: M.ragged_forward(
        model, p, kv, *a, block_size=16, attn_impl="xla"))(
        eng._params, eng.kv, i32(t), i32(t), i32(t), i32(s, 8), i32(s))
    for text in (str(dec), str(rag)):
        assert "loop_pass" not in text and "loop_exit" not in text
        assert text.count("scan[") == 1      # the layer scan alone
    looped, lparams = built(2)
    leng = engine_of(looped, lparams)
    text = str(jax.make_jaxpr(lambda p, kv, *a: M.decode_forward(
        looped, p, kv, *a, block_size=16, attn_impl="xla"))(
        leng._params, leng.kv, i32(s), i32(s), i32(s, 8), jnp.zeros((s,), bool)))
    assert text.count("scan[") == 2          # passes around layers


def test_one_pass_gives_bit_for_bit_what_the_plain_walk_gives():
    """The same weights served as ``total_ut_steps`` 1 through
    ``_scan_layers`` and as the ONE pass of ``_scan_passes`` (a config that
    says 2 passes cut to its first by a threshold of 0 reads pass 0's
    logits): equal bit for bit: the outer loop adds no arithmetic."""
    one, params = built(1)
    two = build_model(dataclasses.replace(
        one.config, total_ut_steps=2, early_exit_threshold=0.0))
    gate = {"kernel": jnp.zeros((64, 1)), "bias": jnp.zeros((1,))}
    a, _ = parity.served_logits(engine_of(one, params), 1, PROMPT[:30], 3)
    b, _ = parity.served_logits(
        engine_of(two, {**params, "exit_gate": gate}), 1, PROMPT[:30], 3)
    assert np.array_equal(a, b)


# ------------------------------------------------------------ the exit rule
@pytest.mark.parametrize("threshold", [0.3, 0.7, 1.0])
def test_the_exit_rule_and_its_counter(family, threshold):
    """Six short sequences (the rows of early positions, whose gates differ
    most), each its prompt and two decode steps: the served logits are
    those of the pass the reference's rule picks (a row that took another
    pass's state reads 0.3 logit-std and more), and ``exit_pass`` counts the
    unembedded rows by that pass. The gate is made steep and biased down so
    that 0.3 and 0.7 send rows to three different passes; at the published
    1.0 every row leaves after the last."""
    model, params = built(4, threshold)
    gate = params["exit_gate"]
    params = {**params, "exit_gate": {
        "kernel": gate["kernel"] * 3.0,
        "bias": jnp.full_like(gate["bias"], -1.0)}}
    eng = engine_of(model, params)
    arch = family.arch(hf(4, threshold))
    # (the reference's gates through ONE walk at a padded length, as
    # ``H.reference`` reads its logits: the walk is causal)
    states = jax.jit(lambda p, x: family.pass_states(arch, p, x))
    counted, worst, elsewhere = np.zeros(4, int), 0.0, 0.0
    for uid, n in enumerate((1, 2, 3, 5, 8, 13)):
        prompt = PROMPT[n:2 * n + 1]
        logits, tokens = parity.served_logits(eng, uid, prompt, 2)
        ids = np.asarray(prompt + tokens, np.int32)
        _h, lam = states(params, jnp.pad(ids, (0, PAD - len(ids))))
        counted += np.bincount(np.asarray(family.exit_pass(
            lam[:, :len(ids)], threshold))[-3:], minlength=4)
        worst = max(worst, parity.row_errors(logits, H.reference(
            params, ids.tolist(), hf(4, threshold))[-3:]).max())
        elsewhere = max(elsewhere, parity.row_errors(logits, H.reference(
            params, ids.tolist(), hf(4, 0.0 if threshold == 1 else 1.0))[-3:]
        ).max())
    assert worst < TOL and elsewhere > 0.3
    stats = eng.loop_stats()
    assert stats["passes"] == 4 and stats["kv_rows"] == 4 * L
    assert stats["exit_pass"] == counted.tolist() and counted.sum() == 18
    if threshold == 1.0:
        assert stats["exit_pass"] == [0, 0, 0, 18]
    else:
        assert (counted > 0).sum() >= 3, "the case exercises too few passes"


def test_the_exit_distribution_sums_to_one_and_the_last_takes_the_rest(
        family):
    lam = jnp.asarray([[0.2, 0.9, 0.0], [0.5, 0.5, 0.0], [0.1, 1.0, 0.0]])
    p = np.asarray(family.exit_distribution(lam))
    assert p.shape == (4, 3)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[:, 0], [0.2, 0.4, 0.04, 0.36], rtol=1e-6)
    assert np.asarray(family.exit_pass(lam, 0.5)).tolist() == [1, 0, 3]
    # at 1.0 the last pass, unless a gate saturates (column 1's third is 1.0:
    # nothing is left for the last pass and the sum reaches 1 there)
    assert np.asarray(family.exit_pass(lam, 1.0)).tolist() == [3, 2, 3]
    h = jnp.zeros((4, 3, 8))
    gate = {"kernel": jnp.zeros((8, 1)), "bias": jnp.zeros((1,))}
    # lam = 0.5 everywhere: the running sum reads 0.5, 0.75, 0.875, 1
    for threshold, want in ((0.5, 0), (0.6, 1), (0.8, 2), (0.9, 3), (1.0, 3)):
        assert np.asarray(M.exit_choice(gate, h, threshold)).tolist() \
            == [want] * 3


# -------------------------------------- what sees blocks works unchanged
def _drive(sess, requests, rounds=600):
    for uid, prompt, budget in requests:
        assert sess.submit(uid, prompt, budget) == "admitted"
    out = {uid: [] for uid, *_ in requests}
    for _ in range(rounds):
        if sess.idle:
            break
        for ev in sess.step():
            if ev.kind == "token":
                out[ev.uid] += ev.tokens
    assert sess.idle
    return out


REQUESTS = [(u, PROMPT[u:u + 18 + 5 * u], 20) for u in range(4)]


def test_eviction_with_requeue_gives_the_tokens_of_a_roomy_pool():
    """A block holds all ``passes x layers`` rows of its tokens, so the
    allocator, eviction and requeue see blocks as for any model: under a
    pool of 8 blocks streams are evicted, prefilled again and finish with
    the tokens a roomy pool gives (the reference's own greedy choice)."""
    model, params = built(2)
    roomy = _drive(ServingSession(
        engine_of(model, params), ServingPolicyConfig(admission="none")),
        REQUESTS)
    eng = engine_of(model, params, num_blocks=8)
    sess = ServingSession(eng, ServingPolicyConfig(
        admission="none", preempt_policy="requeue"))
    tight = _drive(sess, REQUESTS)
    assert sess.stats()["evicted"] > 0
    assert tight == roomy
    assert eng.allocator.free_blocks == 8
    uid, prompt, _ = REQUESTS[2]
    rows = H.reference(params, prompt + roomy[uid], hf(2))
    assert rows[len(prompt) - 1:-1].argmax(-1).tolist() == roomy[uid]


def test_a_prefix_cache_hit_gives_the_same_logits():
    """Two prompts that share 32 tokens (two blocks): the second maps the
    first's blocks, every (pass, layer) row of them, and its logits are
    those of an engine without the cache and the reference's."""
    model, params = built(4)
    shared, tail = PROMPT[:32], PROMPT[32:40]
    cold, _ = parity.served_logits(engine_of(model, params), 2,
                                   shared + tail, 3)
    eng = engine_of(model, params)
    eng.install_prefix_cache()
    parity.served_logits(eng, 1, shared + PROMPT[40:45], 2)
    eng.map_cached_prefix(2, shared + tail)
    assert eng.seqs[2].cached_prefix_len == 32
    rows = [np.asarray(eng.put([2], [(shared + tail)[32:]])[2])]
    tokens = []
    for _ in range(3):
        tokens.append(int(rows[-1].argmax()))
        rows.append(np.asarray(eng.put([2], [[tokens[-1]]])[2]))
    np.testing.assert_allclose(np.stack(rows), cold, atol=1e-5)
    want = H.reference(params, shared + tail + tokens, hf(4))[-4:]
    assert parity.row_errors(np.stack(rows), want).max() < TOL


# ------------------------------------------------------- shapes and records
def test_the_pool_has_a_row_for_every_pass_and_layer():
    model, params = built(4)
    cfg = model.config
    assert cfg.num_kv_layers == 4 * L
    eng = engine_of(model, params)
    assert eng.kv.k.shape == (4 * L, 16 * 16, 4, 16) == eng.kv.v.shape
    assert eng.kv.exit_pass.shape == (4,)
    stats = kv_pool_stats(eng.kv, eng.allocator)
    assert stats["pool_bytes"] == 2 * 4 * L * 256 * 4 * 16 * 4
    assert stats["blocks_total"] == 16
    whole = build_model("ouro-2.6b").config
    assert (whole.num_kv_layers, whole.total_ut_steps) == (192, 4)
    # 2.67 B parameters, the passes' shared weights counted once
    assert 2.66e9 < whole.param_count() < 2.68e9
    assert params["layers"]["attn_post_norm"]["scale"].shape == (L, 64)
    assert params["exit_gate"]["kernel"].shape == (64, 1)


def test_the_round_record_and_the_report_say_passes_and_exits(tmp_path):
    """``passes`` and ``kv_rows`` on every record that launched a forward,
    the device's ``exit_pass`` behind the sampled tokens a record later;
    ``round_phases`` carries them as ``loop`` and ``trace_report
    --requests`` prints one line; a model whose layers run once has none of
    it."""
    import importlib.util
    import os

    from deepspeedsyclsupport_tpu.inference.v2.supervisor import journal_path
    from deepspeedsyclsupport_tpu.monitor import reqtrace

    def served(model, params, where):
        eng = engine_of(model, params)
        sess = ServingSession(eng, ServingPolicyConfig(
            admission="none", journal_path=journal_path(str(where))))
        _drive(sess, REQUESTS[:2])
        return eng, sess.drain_trace()

    eng, records = served(*built(4), tmp_path / "loop")
    rounds = [r["data"] for r in records
              if (r.get("data") or {}).get("stage") == "round"]
    launched = [d for d in rounds if d["program"]]
    assert launched and all(
        (d["passes"], d["kv_rows"]) == (4, 4 * L) for d in launched)
    counted = [d["exit_pass"] for d in rounds if "exit_pass" in d]
    assert counted and all(c[:3] == [0, 0, 0] for c in counted)
    assert [c[3] for c in counted] == sorted(c[3] for c in counted)
    assert 0 < counted[-1][3] <= eng.loop_stats()["exit_pass"][3]
    rp = reqtrace.round_phases([("0", "", records)])
    assert rp["loop"] == {"passes": 4, "kv_rows": 4 * L,
                          "exit_pass": counted[-1]}
    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "trace_report.py")
    spec_ = importlib.util.spec_from_file_location("trace_report", path)
    report = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(report)
    text = report.requests_report(str(tmp_path / "loop"))
    assert f"looped stack: 4 passes a forward over shared weights, " \
           f"{4 * L} cache rows a token; rows by exit pass " \
           f"{counted[-1]}" in text
    plain, precs = served(*_tiny(), tmp_path / "plain")
    assert not any(f in (r.get("data") or {}) for r in precs
                   for f in reqtrace.LOOP_FIELDS)
    assert "loop" not in reqtrace.round_phases([("0", "", precs)])
    assert plain.loop_stats() is None
    assert "looped stack" not in report.requests_report(
        str(tmp_path / "plain"))


def _tiny():
    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


# ------------------------------------------------------------ the refusals
@pytest.mark.parametrize("what,kw", [
    ("layer_pattern", dict(layer_pattern="M*E", num_layers=3,
                           mamba_num_heads=4, mamba_head_dim=8,
                           ssm_state_size=8, num_experts=4)),
    ("experts", dict(num_experts=4)),
    ("leading dense layers", dict(num_experts=4, first_k_dense_replace=1,
                                  num_layers=3)),
    ("hyper-connection streams", dict(hc_mult=2)),
    ("pipeline stages", dict(pipe_stages=2)),
    ("a parallel block", dict(parallel_block=True)),
])
def test_a_looped_stack_refuses_what_it_does_not_walk_by_name(what, kw):
    with pytest.raises(ValueError, match="total_ut_steps") as e:
        ModelConfig(total_ut_steps=2, **kw)
    assert what in str(e.value)


@pytest.mark.parametrize("kw", [dict(total_ut_steps=2),
                                dict(sandwich_norm=True)])
def test_training_refuses_a_looped_stack_by_name(kw):
    model = build_model("tiny", dtype="float32", **kw)
    params = model.init_params()
    batch = {"input_ids": jnp.zeros((1, 8), jnp.int32)}
    with pytest.raises(NotImplementedError, match="looped stack"):
        model.loss(params, batch)
    with pytest.raises(NotImplementedError, match="serving path only"):
        model.apply(params, batch["input_ids"])


# ------------------------------------------------- a stream that ends early
@pytest.fixture(scope="module")
def ending():
    return stream_ends.family(engine_of(*built(2), max_context=48,
                                        num_blocks=12))


@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    stream_ends.check(ending, driver, end)
