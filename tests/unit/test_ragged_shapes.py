"""A mixed round runs at the rows it holds: the rule that gives an engine its
two static shapes of ``ragged_forward`` (``ragged.ragged_shapes``),
the builders at each shape, the choice (``engine_v2._shape_of``), the logits
of one mixed batch across the shapes for a dense, a sparse-expert, a
latent-pool and a hybrid tiny model, what ``warmup()`` compiles, and what
``compiled_programs()`` answers for a program that ran in several shapes."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import ProgramShapes
from deepspeedsyclsupport_tpu.inference.v2.ragged import (
    BlockedAllocator, RaggedShape, SequenceDescriptor, build_ragged_batch,
    ragged_shapes, ssm_pieces, tile_places)
from deepspeedsyclsupport_tpu.inference.v2.scheduler import schedule_chunks
from deepspeedsyclsupport_tpu.models import build_model
from tests.family_harness import Harness

CONFIGS = Path(__file__).parents[2] / "benchmark" / "configs"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------- the rule
@pytest.mark.parametrize("config, budget, seqs, atom_q, want", [
    ("phi-2", 768, 32, 128, [(256, 6), (768, 39)]),
    ("olmoe-1b-7b-d10", 768, 32, 128, [(256, 6), (768, 39)]),
    ("deepseek-v2-ep4-d5", 768, 64, 16, [(256, 20), (768, 113)]),
    ("nemotron3-nano-ep4-d26", 768, 128, 128, [(256, 6), (768, 135)]),
    ("xing4-29b-a4b-d6", 768, 16, 128, [(256, 6), (768, 23)]),
    ("ouro-2.6b", 256, 16, 128, [(128, 5), (256, 19)]),
    (None, 128, 8, 128, [(128, 10)]),     # 128 rows or fewer: ONE shape
    (None, 64, 4, 64, [(64, 6)]),
    (None, 16, 4, 16, [(16, 6)]),
    (None, 129, 4, 128, [(128, 5), (129, 6)]),
    (None, 300, 4, 128, [(128, 5), (300, 7)]),
    (None, 1024, 8, 128, [(256, 6), (1024, 17)]),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_shapes_follow_from_the_budget(config, budget, seqs, atom_q,
                                           want):
    """The six serving configurations of ``benchmark/configs`` (the atom's
    rows as their engines choose them on the chip: 16 under DeepSeek-V2's
    128 heads on a latent pool) and budgets beside them."""
    if config:
        engine = json.loads((CONFIGS / f"{config}.json").read_text())["engine"]
        assert (engine["max_tokens_per_batch"],
                engine["max_sequences"]) == (budget, seqs)
    shapes = ragged_shapes(budget, seqs, atom_q)
    assert [(s.rows, s.atoms) for s in shapes] == want
    assert all(s.pieces == 0 for s in shapes)
    # the largest is today's one shape, worst case and all
    assert shapes[-1] == RaggedShape(budget, seqs + budget // atom_q + 1, 0)
    # no atoms for an attention that takes none; pieces by the same rule
    assert [s.atoms for s in ragged_shapes(budget, seqs)] == [0] * len(want)
    assert [s.pieces for s in ragged_shapes(budget, seqs, 0, atom_q)] \
        == [a for _r, a in want]


def _descs(lengths, cached=None, block_size=16, slots=False):
    alloc = BlockedAllocator(512)
    out = []
    for i, n in enumerate(lengths):
        c = (cached or [0] * len(lengths))[i]
        d = SequenceDescriptor(uid=100 + i, n_cached=c,
                               pending=list(range(1 + i, 1 + i + n)),
                               state_slot=i if slots else None)
        d.blocks = alloc.allocate(-(-(c + n) // block_size))
        out.append((d, n))
    return out


@pytest.mark.parametrize("rows, atoms", [(128, 5), (256, 6), (512, 13)])
def test_the_batch_is_built_at_the_shape_it_is_given(rows, atoms):
    """Arrays of the shape's sizes; the same live content as at the full
    shape, the rest padding the model never reads."""
    S, BQ, BPS = 8, 128, 8
    chunks = _descs([1, 70, 1, 3, 1], cached=[40, 0, 17, 64, 5])
    n = 76
    full = build_ragged_batch(chunks, 512, S, BPS, atom_q=BQ)
    got = build_ragged_batch(chunks, rows, S, BPS, atom_q=BQ, atoms=atoms)
    assert full.atom_qidx.shape == (13, BQ)     # S + T // BQ + 1
    assert got.tokens.shape == got.token_seq.shape == got.token_pos.shape \
        == got.atom_inv.shape == (rows,)
    assert got.atom_qidx.shape == (atoms, BQ)
    assert got.atom_tables.shape == (atoms, BPS)
    assert got.atom_pos0.shape == got.atom_qlen.shape == (atoms,)
    for f in ("tokens", "token_seq", "token_pos"):
        np.testing.assert_array_equal(getattr(got, f)[:n],
                                      getattr(full, f)[:n])
    assert (got.token_seq[n:] == S).all() and got.current_tokens == n
    for f in ("block_tables", "last_tok_idx", "seq_active", "dec_row",
              "dec_len"):
        np.testing.assert_array_equal(getattr(got, f), getattr(full, f))
    assert got.live_atoms == full.live_atoms == 2
    for f in ("atom_qidx", "atom_pos0", "atom_qlen", "atom_tables"):
        np.testing.assert_array_equal(getattr(got, f)[:2],
                                      getattr(full, f)[:2])
    assert not got.atom_qlen[2:].any()
    # a longer chunk's rows find their atom; every other row the dead one
    longer = np.isin(got.token_seq[:n], (1, 3))
    np.testing.assert_array_equal(got.atom_inv[:n][longer],
                                  full.atom_inv[:n][longer])
    assert (np.delete(got.atom_inv, np.flatnonzero(longer))
            == (atoms - 1) * BQ).all()
    assert got.uids == full.uids


@pytest.mark.parametrize("rows, pieces", [(128, 20), (256, 36), (512, 73)])
def test_the_mamba_pieces_follow_the_shape(rows, pieces):
    S, Q = 8, 8
    chunks = _descs([1, 70, 1, 3, 1], cached=[40, 0, 17, 64, 5], slots=True)
    assert tile_places(rows, 512, S, Q) == pieces
    assert [s.pieces for s in ragged_shapes(512, S, 128, Q)] == [20, 73]
    full = ssm_pieces(chunks, 512, S, Q)
    got = ssm_pieces(chunks, rows, S, Q, pieces)
    assert full.row0.shape == (73,) and int(got.count) == 10   # 9 + 1
    for f in ("row0", "length", "slot", "fresh"):
        assert getattr(got, f).shape == (pieces,)
        np.testing.assert_array_equal(getattr(got, f)[:10],
                                      getattr(full, f)[:10])
    assert not got.length[10:].any() and (got.slot[10:] == S).all()
    for f in ("seq_slot", "dec_row", "dec_len", "count"):
        np.testing.assert_array_equal(getattr(got, f), getattr(full, f))


# -------------------------------------------------------- the tiny models
LATENT = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_layers=3, first_k_dense_replace=1, num_heads=4, num_kv_heads=4,
    head_dim=24, vocab_size=512, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
    num_experts_per_tok=3, max_seq_len=512, dtype="float32")
HYBRID = dict(
    hidden_size=32, num_layers=5, layer_pattern="MEM*E", num_heads=4,
    num_kv_heads=2, head_dim=8, vocab_size=128, mamba_num_heads=4,
    mamba_head_dim=8, ssm_state_size=16, ssm_n_groups=2, ssm_conv_kernel=4,
    ssm_chunk_size=8, activation="relu2", mlp_type="mlp",
    intermediate_size=24, moe_intermediate_size=24,
    shared_expert_intermediate_size=48, n_shared_experts=1, num_experts=8,
    num_experts_per_tok=3, max_seq_len=512, dtype="float32")
MODELS = {
    "dense": ("tiny", dict(max_seq_len=512, dtype="float32"), {}),
    "sparse_expert": ("tiny-moe", dict(max_seq_len=512, num_experts=8,
                                       dtype="float32"), {}),
    "latent_pool": ("xing4-29b-a4b", LATENT, {}),
    "hybrid": ("nemotron-3-nano", HYBRID, dict(block_size=8)),
}


# -------------------------------------------------------------- the choice
ENGINE = dict(max_context=512, num_blocks=96, block_size=16,
              max_tokens_per_batch=512, prefill_attn="kernel_interpret",
              decode_attn="xla")


engine_of = Harness(None, ENGINE).engine_of


@pytest.fixture(scope="module")
def dense():
    model = build_model("tiny", max_seq_len=512, dtype="float32")
    return model, model.init_params(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def choosers(dense):
    """Two engines of a 768-row budget: one whose attention takes atoms, one
    whose does not."""
    kw = dict(max_tokens_per_batch=768, max_sequences=40, max_context=1024,
              num_blocks=8)
    eng = engine_of(*dense, **kw)
    assert [tuple(s) for s in eng._shapes] == [(256, 6, 0), (768, 47, 0)]
    return eng, engine_of(*dense, prefill_attn="xla", **kw)


@pytest.mark.parametrize("lengths, rows", [
    ([1] * 31 + [96], 256),           # a decode-sat round: 127 rows, 1 atom
    ([1] * 31 + [225], 256),          # the last row of the shape
    ([1] * 31 + [226], 768),          # falls through on TOKENS
    ([100], 256), ([256], 256), ([257], 768), ([385], 768), ([768], 768),
    ([2] * 5, 256),                   # five chunk tails: the 256 shape's all
    ([2] * 6, 768), ([2] * 7, 768),   # ... falls through on ATOMS
    ([2] * 33, 768),                  # 66 rows, 33 atoms
    ([100, 100, 2, 2, 2], 256), ([100, 100, 2, 2, 2, 2], 768),
    ([1] * 40, 256),                  # one-token prompts take no atom
], ids=lambda v: f"{len(v)}x{sum(v)}" if isinstance(v, list) else str(v))
def test_a_round_takes_the_smallest_shape_that_holds_it(choosers, lengths,
                                                        rows):
    eng, plain = choosers
    assert eng._shape_of(lengths).rows == rows
    # an attention that takes no atoms is held to its rows alone
    assert plain._shape_of(lengths).rows == next(
        r for r in (256, 768) if sum(lengths) <= r)


def test_a_hybrid_round_is_held_to_its_pieces_too():
    model = build_model("nemotron-3-nano", **{**HYBRID, "ssm_chunk_size": 32})
    eng = engine_of(model, model.init_params(jax.random.PRNGKey(0)),
                    max_sequences=8, prefill_attn="xla")
    assert [tuple(s) for s in eng._shapes] == [(128, 0, 8), (512, 0, 25)]
    assert eng._shape_of([1] * 6 + [100]).rows == 128     # 4 pieces
    assert eng._shape_of([30] * 4).rows == 128            # 4
    assert eng._shape_of([33] * 3 + [2]).rows == 128      # 7
    assert eng._shape_of([33] * 3 + [2, 2]).rows == 512   # 8
    assert eng._shape_of([2] * 8).rows == 512             # 8
    assert eng._shape_of([60, 60]).rows == 128            # 4
    assert eng._shape_of([33] * 6).rows == 512            # 198 rows


# ------------------------------------------- one batch, every shape's logits
@pytest.fixture(scope="module", params=list(MODELS))
def mixed_round(request):
    """An engine of two shapes (128 / 512 rows) in front of ONE
    mixed round: two sequences that decode over a cached context, a prompt's
    first 47 tokens and a two-token tail: ``(engine, chunks)``, the chunks
    scheduled and funded, nothing run."""
    preset, overrides, engine = MODELS[request.param]
    model = build_model(preset, **overrides)
    eng = engine_of(model, model.init_params(jax.random.PRNGKey(2)),
                    max_sequences=6, **engine)
    assert [s.rows for s in eng._shapes] == [128, 512]
    rng = np.random.default_rng(5)
    vocab = model.config.vocab_size
    eng.put([1, 2], [rng.integers(1, vocab, 21).tolist(),
                     rng.integers(1, vocab, 9).tolist()])
    cfg = eng.config
    eng._enqueue([1, 2, 3, 4],
                 [[7], [9], rng.integers(1, vocab, 47).tolist(), [5, 6]],
                 strict=True)
    chunks = schedule_chunks(
        list(eng.seqs.values()), eng.allocator,
        max_tokens=cfg.max_tokens_per_batch, max_sequences=cfg.max_sequences,
        block_size=cfg.block_size, max_context=cfg.max_context,
        max_prefill_fraction=cfg.max_prefill_fraction)
    assert sorted(n for _d, n in chunks) == [1, 1, 2, 47]
    return request.param, eng, chunks


def _run_at(eng, chunks, pool, rows):
    """The round's logits with its batch built at the ``rows`` shape, from
    the pool as the round found it (a forward donates the pool it gets)."""
    eng.kv = jax.tree_util.tree_map(jnp.copy, pool)
    eng._rows_floor = rows
    try:
        return np.asarray(eng._run(chunks))[:len(chunks)]
    finally:
        eng._rows_floor = 0


def test_the_logits_of_one_round_agree_across_the_shapes(mixed_round):
    """A smaller shape differs from the largest only in padding the model
    never reads: pad rows (``token_seq == S``) and dead atoms
    (``atom_qlen == 0``)."""
    name, eng, chunks = mixed_round
    pool = jax.tree_util.tree_map(jnp.copy, eng.kv)
    got = {s.rows: _run_at(eng, chunks, pool, s.rows) for s in eng._shapes}
    assert set(eng._dispatched["ragged_forward"][1]) == {128, 512}
    assert np.isfinite(got[512]).all() and np.ptp(got[512]) > 0.1
    np.testing.assert_allclose(got[128], got[512], rtol=2e-5, atol=2e-5,
                               err_msg=name)
    # the round itself runs at the smallest
    assert eng._shape_of([n for _d, n in chunks]).rows == 128


def test_every_shape_is_in_the_compiled_programs(mixed_round):
    """``compiled_programs()["ragged_forward"]`` after several shapes ran:
    the text of every one (so the benchmark's scope readers find the
    labelled instructions of whichever shape the trace holds) and the
    memory of the largest."""
    from benchmark import flops, scopes

    name, eng, chunks = mixed_round
    pool = jax.tree_util.tree_map(jnp.copy, eng.kv)
    for rows in (128, 512):
        _run_at(eng, chunks, pool, rows)
    ran = sorted(eng._dispatched["ragged_forward"][1])
    programs = eng.compiled_programs()
    both = programs["ragged_forward"]
    assert isinstance(both, ProgramShapes) and len(both.by_rows) == len(ran)
    assert both.as_text().startswith("HloModule jit_ragged_forward,")
    assert both.as_text().count("HloModule jit_ragged_forward,") == len(ran)
    assert flops.program_bytes(both) == flops.program_bytes(both.by_rows[-1])
    assert flops.program_bytes(both) \
        >= max(map(flops.program_bytes, both.by_rows[:-1]))
    labels = {"dense": (), "sparse_expert": ("moe_route", "moe_experts",
                                             "moe_combine"),
              "latent_pool": ("mla_proj", "mla_absorb", "moe_experts"),
              "hybrid": ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")}[name]
    merged = scopes.instructions_under(both.as_text(), labels)
    small, large = (scopes.instructions_under(c.as_text(), labels)
                    for c in both.by_rows)
    assert set(small.values()) == set(large.values()) == set(labels)
    # every name the largest shape has reads as the largest shape uses it,
    # labelled or not; a name only the smaller has keeps its label
    in_large = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ",
                              both.by_rows[-1].as_text(), re.M))
    assert set(large) <= in_large
    assert merged == {**{k: v for k, v in small.items()
                         if k not in in_large}, **large}
    # a program that ran in one shape answers as it always did
    assert not isinstance(programs.get("decode_forward"), ProgramShapes)


class _Text:
    def __init__(self, text, temp):
        self._text, self.temp = text, temp

    def as_text(self):
        return self._text

    def memory_analysis(self):
        return self.temp


def test_one_name_in_two_shapes_reads_as_the_largest_uses_it():
    """By hand: ``fusion.1`` is the router in the smaller shape and nothing
    labelled in the largest, ``fusion.2`` an expert GEMM in both, ``fusion.3``
    only the smaller shape's, ``fusion.9`` only the largest's."""
    from benchmark import scopes

    def line(name, scope):
        return (f'  %{name} = f32[8]{{0}} fusion(%p), kind=kLoop, '
                f'metadata={{op_name="jit(ragged_forward)/{scope}/mul"}}')

    small = _Text("\n".join([
        "HloModule jit_ragged_forward, entry={...}", "ENTRY %main {",
        line("fusion.1", "moe_route"), line("fusion.2", "moe_experts"),
        "  ROOT %fusion.3 = " + line("x", "moe_combine").split(" = ", 1)[1],
        "}"]), 1)
    large = _Text("\n".join([
        "HloModule jit_ragged_forward, entry={...}", "ENTRY %main {",
        line("fusion.1", "layers"), line("fusion.2", "moe_experts"),
        line("fusion.9", "moe_route"), "}"]), 2)
    both = ProgramShapes([small, large])
    assert scopes.instructions_under(
        both.as_text(), ("moe_route", "moe_experts", "moe_combine")) == {
            "fusion.2": "moe_experts", "fusion.3": "moe_combine",
            "fusion.9": "moe_route"}
    assert both.as_text().endswith(large.as_text())
    assert both.memory_analysis() == 2


# ------------------------------------------------------------ the warm-up
COMPILES = []


@pytest.fixture(scope="module")
def warm(dense):
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: COMPILES.append(name)
        if name == COMPILE_EVENT else None)
    eng = engine_of(*dense, max_sequences=4)
    eng.warmup()
    assert set(eng._dispatched["ragged_forward"][1]) == {128, 512}
    assert eng._rows_floor == 0 and not eng.seqs
    return eng


@pytest.mark.parametrize("prompt, rows", [(39, 128), (127, 128), (200, 512)])
def test_after_warmup_a_round_of_any_shape_compiles_nothing(warm, prompt,
                                                            rows):
    """As ``benchmark/run.py`` counts ``compiles_in_window``: a mixed round
    of each shape beside a decoding sequence, then a pure decode round."""
    eng = warm
    uid = 10 * rows
    eng.put([uid], [[3, 4, 5]])
    before = len(COMPILES)
    assert eng._shape_of([1, prompt]).rows == rows
    out = eng.put([uid, uid + 1], [[7], list(range(1, prompt + 1))])
    assert set(out) == {uid, uid + 1}
    eng.put([uid, uid + 1], [[8], [9]])
    jax.block_until_ready(eng.kv)
    assert len(COMPILES) == before
    eng.flush([uid, uid + 1])


def test_a_budget_of_128_rows_or_fewer_compiles_what_it_always_did(dense):
    eng = engine_of(*dense, max_tokens_per_batch=128, max_sequences=4)
    assert [tuple(s) for s in eng._shapes] == [(128, 6, 0)]
    eng.warmup()
    assert list(eng._dispatched["ragged_forward"][1]) == [128]
    assert not isinstance(eng.compiled_programs()["ragged_forward"],
                          ProgramShapes)


def test_the_round_record_says_the_rows_the_forward_ran_at(dense):
    from deepspeedsyclsupport_tpu.inference.v2 import (ServingPolicyConfig,
                                                       ServingSession)

    eng = engine_of(*dense, max_sequences=4)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(1, list(range(1, 31)), max_new_tokens=3)
    sess.step()
    sess.submit(2, list(range(1, 201)), max_new_tokens=2)
    while not sess.idle:
        sess.step()
    rounds = [r["data"] for r in sess.drain_trace()
              if r.get("name") == "serve/stage"
              and r["data"].get("stage") == "round" and r["data"]["program"]]
    assert [(d["program"], d["tokens"], d["rows"]) for d in rounds[:3]] == [
        ("ragged_forward", 30, 128), ("ragged_forward", 201, 512),
        ("decode_forward", 2, 4)]
    sess.close()
