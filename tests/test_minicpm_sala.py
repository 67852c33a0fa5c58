"""``model_type: minicpm_sala`` on the serving path, at tiny widths that keep
the structure (two periods of one block-sparse attention layer and three
lightning layers, each followed by a dense MLP; sparse sizes scaled to kernel
4 / stride 2 / block 8 / 6 blocks read of which the first and a window of 2 /
``dense_len`` 72: ISSUE 59's 40 is five blocks, fewer than the six a row
reads, and would leave the dense branch nothing to decide), float32, on the CPU: the program (``build_model`` ->
``InferenceEngineV2``, chunked prefill through the chunked lightning form and
the selection a row, decode through the state pool, the KV pool and the
pooled keys) against the plain reference
``benchmark/families/minicpm_sala.py`` on seeded weights with every leaf
moved off its init; both routes; a mixed round; a state slot reused; planted
faults, each refused; the refusals' messages. ``ops/sparse_block.py``'s
functions and ``ops/ssm.py``'s lightning entries against their twins are
``tests/unit/test_sparse_block.py``'s, eviction, requeue and idle
``tests/unit/test_bsa_cache.py``'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.family_harness import (Harness, ending, engines,  # noqa: F401
                                  family, moved)
from tests.unit import stream_ends

HF = {
    "model_type": "minicpm_sala", "hidden_size": 64, "num_hidden_layers": 8,
    "mixer_types": (["minicpm4"] + ["lightning-attn"] * 3) * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_use_rope": True, "attn_use_rope": False, "qk_norm": True,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "attention_bias": False,
    "vocab_size": 128, "intermediate_size": 96, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 16,
    "tie_word_embeddings": False,
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                      "topk": 4, "init_blocks": 1, "window_size": 16,
                      "dense_len": 72}}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 64,
          "block_size": 8, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
KERNELS = {"prefill_attn": "kernel_interpret",
           "decode_attn": "pallas_interpret"}
# both sides are float32 and differ in the order of summation and in the
# FORM of the recurrence (a piece's quadratic form against token by token):
# measured 1.7e-6 logit-std with every leaf moved by 0.2 on either route;
# the planted misreadings measure 9e-4 (a bf16 state) to 1.0
TOL = 2e-5
# 7 tokens (the dense branch alone, one chunk) and 90 (five chunks of 16 and
# one of 10: windows that straddle chunks and pages, rows either side of
# dense_len in one chunk, of which those at 48-70 read 7 to 9 blocks where a
# selection would read 6; 12 blocks of which 6 are read at the end)
PROMPTS = ([7, 3, 11, 100, 41, 9, 5],
           np.random.default_rng(0).integers(0, 128, 90).tolist())
ENDING = {"max_context": 32, "num_blocks": 12}
H = Harness(HF, ENGINE, PROMPTS)


def overrides(family, hf=HF):
    return {**family.program_widths(hf), "max_seq_len": 256,
            "dtype": "float32", "lightning_chunk_size": 8}


@pytest.fixture(scope="module")
def built(family):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("minicpm-sala", **overrides(family))
    model.seed = 3
    return model, moved(jax.jit(model.init_params)())


# ------------------------------------------------------------ the structure
def test_the_pattern_is_two_characters_a_published_layer(family):
    from deepspeedsyclsupport_tpu.inference.v2.model import layer_plan
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("minicpm-sala")
    sparse = (0, 9, 16, 17, 22, 29, 30, 31)
    assert whole.layer_pattern == "".join(
        ("*" if l in sparse else "L") + "F" for l in range(32))
    assert whole.num_layers == 64
    assert (whole.num_kv_layers, whole.state_layers, whole.pattern_count("F"),
            whole.state_chunk_size, whole.num_moe_layers) == (8, 24, 32, 128,
                                                              0)
    # the cell's cut, layers 9-20: its runs of LF pairs scan
    cut = "*FLFLFLFLFLFLF*F*FLFLFLF"
    assert layer_plan(cut) == [("*", 1), ("FL", 6), ("F*", 2), ("FL", 3),
                               ("F", 1)]
    assert family.layer_pattern(HF) == "*FLFLFLF" * 2
    assert family.program_widths(HF)["num_layers"] == 16
    # 9.48 B parameters whole; muP's three scalings
    assert whole.param_count() / 1e9 == pytest.approx(9.48, abs=0.01)
    assert (whole.embed_scale, whole.logit_scale) == (12.0, 1 / 16)
    assert whole.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (whole.sparse_block_topk, whole.sparse_block_window,
            whole.sparse_block_init, whole.sparse_block_dense_len) \
        == (96, 32, 1, 8192)


def test_four_stacks_and_their_leaves(built):
    model, _ = built
    params = model.init_params()
    cfg = model.config
    assert (cfg.pattern_count("L"), cfg.pattern_count("F"),
            cfg.num_kv_layers, cfg.pattern_count("E")) == (6, 8, 2, 0)
    l, f, a = (params["lightning_layers"], params["ffn_layers"],
               params["attn_layers"])
    assert set(l) == {"norm", "wq", "wk", "wv", "wz", "q_norm", "k_norm",
                      "o_norm", "wo"}
    assert l["wq"].shape == l["wz"].shape == (6, 64, 64)
    assert l["q_norm"]["scale"].shape == (6, 16)
    assert l["o_norm"]["scale"].shape == (6, 64)
    assert set(f) == {"mlp_norm", "mlp"}
    assert f["mlp"]["w_gate"].shape == (8, 64, 96)
    assert set(a) == {"attn_norm", "attn"}
    assert set(a["attn"]) == {"wq", "wk", "wv", "wo", "w_g", "q_norm",
                              "k_norm"}
    assert a["attn"]["wk"].shape == (2, 64, 32)
    assert a["attn"]["q_norm"]["scale"].shape == (2, 16)
    assert not params["layers"] and "lm_head" in params
    # the draw keeps muP's proportion: a projection back into the stream is
    # drawn embed_scale / residual_scale times the hybrid stacks' rule
    rule = 0.02 / np.sqrt(64 * 16)
    assert float(jnp.std(l["wo"])) == pytest.approx(
        rule * 12 / cfg.residual_scale, rel=0.05)


@pytest.mark.parametrize("wrong, says", [
    (dict(layer_pattern="*FLFMFLF" * 2), "no 'M' or 'K' layer beside them"),
    (dict(lightning_heads=0), "'L' layers need lightning_heads"),
    (dict(layer_pattern="*FLFLFLX" * 2), "'L' \\(lightning"),
    (dict(layer_pattern=None, num_layers=16), "belong to a layer_pattern"),
    (dict(sparse_block_stride=3), "the stride divides the kernel"),
    (dict(sparse_block_dense_len=16), "holds those apart"),
    (dict(sparse_block_topk=2), "sparse_block_topk\\) hold the first"),
    (dict(qkv_bias=True), "no indexer, alibi or attention bias"),
])
def test_what_the_pattern_refuses_says_why(built, wrong, says):
    cfg = built[0].config
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(cfg, **wrong)


def test_the_pool_refuses_a_page_that_is_not_the_sparse_block(built):
    with pytest.raises(ValueError, match="block_size must equal "
                       "sparse_block_size 8"):
        H.engine_of(*built, block_size=16)


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_chunked_prefill_then_decode_match_the_reference(built, engines,
                                                         monkeypatch, route):
    """90 tokens = five chunks of 16 and one of 10, in pieces of 8 (every
    chunk after the first starts from the slot's state; windows straddle
    chunks and pages; the rows of the fifth chunk stand either side of
    ``dense_len``), then six decode steps through the state pool, the KV
    pool and the pooled keys. ``kernels``: the atoms under the selection's
    mask, the one-token rows over their own page tables and the state step,
    all interpreted."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg

    if route == "kernels":
        first = dataclasses.replace(
            reg.get_impl("ssm_step", "pallas_interpret"), name="first",
            priority=100, auto_eligible=lambda ctx: True)
        monkeypatch.setitem(reg._REGISTRY["ssm_step"], "first", first)
        assert model_v2._ssm_step_fn() is first.fn
        err = H.served_errors(*built, **KERNELS)
    else:
        err = H.served_errors(*built, eng=engines())
    assert err < TOL


def test_a_mixed_round_and_a_slot_reused(built, engines):
    H.check_a_mixed_round_and_a_slot_reused(built[1], engines(), TOL)


def test_the_devices_counts_are_the_selections_own(built, engines):
    """``kv.bsa`` after a forward, under ``bsa.COUNTS``: a 16-row chunk at
    positions 64-79 (the first seven under ``dense_len``: all 9 blocks; the
    rest 6 blocks a row and group) in 2 sparse layers, every row a tile of
    its own on this route."""
    from deepspeedsyclsupport_tpu.inference.v2.bsa import COUNTS

    eng = engines()
    assert eng.round_tail() == (eng.kv.bsa,)
    eng.put([0], [PROMPTS[1][:80]])
    got = eng.tail_fields(np.asarray(eng.kv.bsa).tolist())
    assert list(got) == list(COUNTS)
    pos = np.arange(64, 80)
    read = np.where(pos + 1 < 72, pos // 8 + 1, 6)
    assert got["bsa_rows"] == 2 * 16
    assert got["bsa_windows"] == 2 * int(((pos + 1 - 4) // 2 + 1).sum())
    assert got["bsa_pages"] == got["bsa_row_pages"] == 2 * 2 * int(read.sum())
    assert got["bsa_pairs"] == 2 * 2 * int(
        ((read - 1) * 8 + pos % 8 + 1).sum())
    assert got["bsa_visible_blocks"] == 2 * 2 * int((pos // 8 + 1).sum())
    eng.flush([0])


# ------------------------------------------------------------ planted faults
def _all_fresh(fn, at):
    """An entry that takes ``pieces`` at ``at`` with every piece told it is
    its sequence's first: nothing carried from piece to piece."""
    def wrong(*args):
        args = list(args)
        row0, length, slot, fresh, count = args[at]
        args[at] = (row0, length, slot, jnp.ones_like(fresh), count)
        return fn(*args)
    return wrong


def _plant(monkeypatch, fault):
    from deepspeedsyclsupport_tpu.inference.v2 import kv_cache
    from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
    from deepspeedsyclsupport_tpu.ops import sparse_block, ssm

    if fault == "a_selection_a_head_not_a_group":
        # (the group's first head's choice for all of them)
        monkeypatch.setattr(sparse_block, "group_sum", lambda p: p[:, 0])
    elif fault == "max_pooled_keys":
        monkeypatch.setattr(sparse_block, "window_key",
                            lambda keys: keys.astype(jnp.float32).max(1))
    elif fault == "the_windows_blocks_not_forced":
        monkeypatch.setattr(
            sparse_block, "forced_blocks", lambda pos, blocks, sizes:
            jnp.broadcast_to(jnp.arange(blocks) < sizes.init,
                             (*pos.shape, blocks)))
    elif fault == "a_window_that_straddles_two_chunks_dropped":
        write = sparse_block.pool_write
        # a chunk is 16 rows from a multiple of 16: a window whose first
        # key lies in the chunk before
        monkeypatch.setattr(
            sparse_block, "pool_write", lambda ck, k, layer, tables, seq,
            pos, live, sizes: write(ck, k, layer, tables, seq, pos,
                                    live & (pos % 16 >= sizes.kernel - 1),
                                    sizes))
    elif fault == "no_rotation_in_the_lightning_layer":
        monkeypatch.setattr(model_v2, "apply_rope",
                            lambda t, *args, **kw: t)
    elif fault == "a_decay_factor_a_layer":
        decay = ssm.lightning_decay
        monkeypatch.setattr(ssm, "lightning_decay",
                            lambda heads: 0.5 * decay(heads))
    elif fault == "state_zeroed_between_pieces":
        monkeypatch.setattr(ssm, "lightning_pieces",
                            _all_fresh(ssm.lightning_pieces, 5))
    elif fault == "state_in_bf16":
        monkeypatch.setattr(kv_cache, "LIGHTNING_STATE_DTYPE", jnp.bfloat16)


FAULTS = {
    "a_selection_a_head_not_a_group": {}, "max_pooled_keys": {},
    "the_windows_blocks_not_forced": {},
    "64_by_score_not_63": {"sparse_block_topk": 7},
    "a_window_that_straddles_two_chunks_dropped": {},
    "dense_len_ignored": {"sparse_block_dense_len": 24},
    "rotation_on_the_sparse_layer": {"pos_embed": "rope"},
    "no_rotation_in_the_lightning_layer": {},
    "a_decay_factor_a_layer": {}, "state_zeroed_between_pieces": {},
    "r_from_the_cuts_depth": {"residual_scale": 1.4 / 8 ** 0.5},
    "attention_gate_left_out": {"attn_out_gate": False},
    "state_in_bf16": {},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_refused(built, monkeypatch, fault):
    """Each misreading of the publication, served, against the reference of
    the RIGHT program: beyond the tolerance by an order or more. The
    90-token prompt runs in six chunks and twelve pieces and ends 18 tokens
    past ``dense_len``, so what is not carried between pieces, what a
    dropped window would have scored and which blocks are read all show in
    the logits of its last position: the prefill alone is compiled and
    run."""
    from deepspeedsyclsupport_tpu.models import build_model

    model, params = built
    if FAULTS[fault]:
        model = build_model(dataclasses.replace(model.config,
                                                **FAULTS[fault]))
    _plant(monkeypatch, fault)
    err = H.served_errors(model, params, PROMPTS[1:], 0)
    assert err > 10 * TOL, err


# ------------------------------------------------------------------ scopes
def test_the_mixers_scopes_reach_the_compiled_programs(engines):
    """What the per-layer readers find by (``benchmark/scopes.py``): the
    ``bsa_*`` and ``la_*`` scopes and the attention's gate in both
    forwards, the state step under ``la_step`` INSIDE ``la_scan`` in both,
    the pieces under ``la_chunk`` inside ``la_scan`` in the ragged forward
    alone (a decode step has no piece)."""
    from benchmark import scopes

    eng = engines()
    eng.warmup()
    labels = ("bsa_pool", "bsa_score", "bsa_select", "bsa_attend",
              "la_proj", "la_gate", "la_step", "attn_gate", "la_chunk")
    found = {name: set(scopes.instructions_under(c.as_text(), labels)
                       .values())
             for name, c in eng.compiled_programs().items()}
    assert found["decode_forward"] == set(labels[:8])
    assert found["ragged_forward"] == set(labels)
    text = eng.compiled_programs()["ragged_forward"].as_text()
    paths = [p for _n, p in scopes._INSTRUCTION.findall(text)]
    for inner in ("la_chunk", "la_step"):
        mine = [p for p in paths if inner in p.split("/")]
        assert mine and all("la_scan" in p.split(f"/{inner}/")[0].split("/")
                            for p in mine), inner
    assert not any("ssm_" in p or "kda_" in p or "dsa_" in p for p in paths)


# ---------------------------------------------------------------- refusals
def test_what_a_model_with_a_lightning_state_refuses_says_why(
        built, engines, tmp_path):
    model, params = built
    eng = engines()
    with pytest.raises(NotImplementedError, match="lightning, Mamba-2.*"
                       "snapshot of the recurrent state at every shared "
                       "block boundary"):
        eng.install_prefix_cache()
    with pytest.raises(NotImplementedError, match="serialize.*snapshot of "
                       "the recurrent state beside the parameters"):
        eng.serialize(str(tmp_path / "snap"))
    with pytest.raises(NotImplementedError,
                       match="lightning.*block-sparse attention.*chunked "
                       "scan's backward is not written"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))


def test_the_pools_and_their_stats(engines):
    eng = engines()
    kv = eng.kv
    # [lightning layers, slots + the sink, heads, key channels, value ones]
    assert kv.la_s.shape == (6, 5, 4, 16, 16) and kv.la_s.dtype == jnp.float32
    # [sparse layers, pages, windows that start in a page, KV heads, dim]
    assert kv.ck.shape == (2, 64, 4, 2, 16) and kv.k.shape == (2, 512, 2, 16)
    assert kv.bsa.shape == (7,) and kv.moe is None
    per_slot = 6 * 4 * 16 * 16 * 4
    assert eng.state_stats() == {
        "bytes_per_slot": per_slot, "slots": 4, "slots_live": 0,
        "dtype": "float32", "layers": 6, "pool_bytes": per_slot * 5}
    assert kv.state_names == ("la_s",) and kv.state_kind == "la"
    assert kv.state_slots == 4 and len(kv.pools) == 3
    # a cached token: K and V of 2 sparse layers + a pooled key every 2
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats

    stats = kv_pool_stats(kv, eng.allocator)
    assert stats["pool_bytes"] == 512 * 2 * (2 * 2 * 16 * 4) \
        + 64 * 4 * 2 * (2 * 16 * 4)
    eng.warmup()
    assert eng.state_stats()["slots_live"] == 0
    assert sorted(eng._state_free) == [0, 1, 2, 3]
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


# ------------------------------------------------- a stream that ends early
@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    """The state slots too: ``stream_ends`` counts them back, and a new
    stream in a released slot starts from zeros."""
    stream_ends.check(ending, driver, end)
