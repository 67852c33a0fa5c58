"""``model_type: glm_moe_dsa`` (GLM-5) on the serving path, at tiny widths
that keep the structure (one leading dense layer and two expert layers;
latent attention whose values are wider than its un-rotated keys; an indexer
whose queries read the query latent and whose heads rotate half their dims;
half the router's experts held), float32, on the CPU: the program
(``build_model`` -> ``InferenceEngineV2``, chunked prefill through the
selection's mask over a LATENT pool, decode through the gather of the
selected latent rows) against the plain reference
``benchmark/families/glm_moe_dsa.py`` on seeded weights with every leaf
moved off its init, at contexts of 5 x ``index_topk``; a mixed round through
a session; what a latent pool beside an indexer holds. The six planted
faults are ``tests/unit/test_glm5_faults.py``'s, the shares and the counts
``tests/benchmark/test_glm5.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity
from deepspeedsyclsupport_tpu.inference.v2 import (ServingPolicyConfig,
                                                   ServingSession, dsa)
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats
from tests.family_harness import (Harness, engines, family,  # noqa: F401
                                  moved)

TOPK, V = 8, 128
HF = {"model_type": "glm_moe_dsa", "hidden_size": 64, "intermediate_size": 96,
      "moe_intermediate_size": 32, "num_hidden_layers": 3,
      "first_k_dense_replace": 1, "num_attention_heads": 4,
      "num_key_value_heads": 4, "head_dim": 8, "q_lora_rank": 24,
      "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8,
      "qk_head_dim": 20, "v_head_dim": 16, "index_topk": TOPK,
      "index_n_heads": 2, "index_head_dim": 16,
      "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
      "vocab_size": V, "n_routed_experts": 4, "n_shared_experts": 1,
      "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1,
      "moe_layer_freq": 1, "attention_bias": False, "norm_topk_prob": True,
      "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
      "topk_method": "noaux_tc", "rms_norm_eps": 1e-5,
      "tie_word_embeddings": False,
      # the chip's share: 4 of the router's 8 experts
      "reduced": {"n_routed_experts": {"published": 8, "run": 4}}}
ATTN = {"xla": dict(prefill_attn="xla", decode_attn="xla"),
        "kernels": dict(prefill_attn="kernel_interpret",
                        decode_attn="pallas_interpret", atom_q_size=8)}
ENGINE = {"max_context": 64, "max_sequences": 4, "num_blocks": 48,
          "block_size": 4, "max_tokens_per_batch": 16, **ATTN["xla"]}
# both sides are float32 and differ in the order of summation and in the
# FORM of the attention (absorbed over a cache against expanded without):
# measured 7.5e-6 logit-std with every leaf moved by 0.2; a row that
# attends a wrong set reads 0.05 to several
TOL = 5e-5
PROMPT = np.random.default_rng(0).integers(0, V, 60).tolist()
H = Harness(HF, ENGINE, [PROMPT[:41]])


def overrides(family, hf=HF):
    widths = family.program_widths(hf)
    return {**{k: v for k, v in widths.items() if k != "experts_held"},
            "num_experts_held": widths["experts_held"], "num_kv_heads": 4,
            "max_seq_len": 128, "dtype": "float32",
            # the experts at full weight (every leaf is moved besides)
            "routed_write_share": None}


@pytest.fixture(scope="module")
def built(family):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("glm-5", **overrides(family))
    model.seed = 3
    return model, moved(jax.jit(model.init_params)())


def dense_reference(family, params, ids):
    """Another selection (every key a row sees): the family's blocks, walked
    by hand (one jitted walk)."""
    from benchmark import reference as ref

    arch = family.arch(HF)

    def walk(params, ids):
        with jax.default_matmul_precision("highest"):
            x = params["embed"]["embedding"][ids]
            for name in ("dense_layers", "layers"):
                for j in range(params[name]["attn_norm"]["scale"].shape[0]):
                    p = jax.tree_util.tree_map(lambda w: w[j], params[name])
                    x = family.block(arch, p, x, select="all")[0]
            h = ref.rms_norm(params["final_norm"], x, arch["norm_eps"])
            return h @ params["lm_head"]["kernel"]
    return np.asarray(jax.jit(walk)(params, jnp.asarray(ids)))


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("attn", sorted(ATTN))
def test_served_logits_are_the_references(family, built, engines, attn):
    """A 41-token prompt prefilled in chunks of 16, 16 and 9 rows (contexts
    to 5 x ``topk``), then three of its own greedy tokens through the pool:
    every row's logits are the full forward's. ``kernels``: the scores, the
    selection and the ragged kernel's LATENT tile under the selection's
    mask in interpret mode over atoms of 8 rows, the one-token rows through
    the gather of the selected latent rows."""
    eng = engines(**ATTN[attn])
    served, tokens = parity.served_logits(eng, 0, PROMPT[:41], 3)
    want = H.reference(built[1], PROMPT[:41] + tokens)[40:]
    assert parity.row_errors(served, want).max() < TOL
    assert {"ragged_forward", "decode_forward"} <= set(eng._dispatched)
    # and the selection MATTERS here: dense attention reads otherwise
    dense = dense_reference(family, built[1], PROMPT[:41] + tokens)[40:]
    assert parity.row_errors(dense, want).max() > 0.02


def test_within_topk_the_attention_is_dense(family, built, engines):
    """While ``t + 1 <= topk`` a row attends everything it sees."""
    served, _ = parity.served_logits(engines(), 0, PROMPT[:TOPK], 0)
    dense = dense_reference(family, built[1], PROMPT[:TOPK])[-1:]
    assert parity.row_errors(served, dense).max() < TOL


def test_a_mixed_round_serves_a_prompt_beside_a_decode(built, engines):
    """Sequence A decodes while B's prompt comes in beside it: atoms and a
    one-token row in ONE ``ragged_forward`` through the kernels' route (the
    row's selected latent rows gathered, the atoms under the mask), each
    against the reference; and the round record says what was selected."""
    eng = engines(**ATTN["kernels"])
    a, b = PROMPT[:19], PROMPT[19:50]
    la = [np.asarray(eng.put([1], [a])[1])]
    tok = int(la[-1].argmax())
    out = eng.put([1, 2], [[tok], b], drain=False)        # a mixed round
    assert 1 in out and 2 not in out
    la.append(np.asarray(out[1]))
    lb = np.asarray(eng.put([], [])[2])                   # b's last chunk
    want_a = H.reference(built[1], a + [tok])
    assert parity.row_errors(np.stack(la), want_a[-2:]).max() < TOL
    assert parity.row_errors(
        lb[None], H.reference(built[1], b)[-1:]).max() < TOL
    eng.flush([1, 2])


def test_a_session_counts_what_the_attention_reads(built, engines):
    eng = engines()
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(0, PROMPT[:30], 4)
    while not sess.idle:
        sess.step()
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round" and r["data"]["program"]]
    assert rounds and all("sel_pairs" in d and "dec_sel_tokens" in d
                          for d in rounds)
    # (what the device counted of a forward rides behind the NEXT round's
    # sampled tokens: the held share's rows are on those records)
    assert any(d.get("moe_rows") for d in rounds)
    assert any(d["sel_pairs"] for d in rounds) \
        and any(d["dec_sel_tokens"] == TOPK for d in rounds)
    assert all(d["sel_pairs"] <= d["attn_pairs"]
               and d["dec_sel_tokens"] <= d["dec_ctx_tokens"] for d in rounds)
    assert eng.moe_stats()["held"].tolist() == [0, 1, 2, 3]
    sess.close()


# ------------------------------------------------------------------ the pool
def test_the_pool_is_a_latent_row_and_an_indexer_key_a_token(built):
    eng = H.engine_of(*built)       # (a pool of zeros: nothing has run)
    kv = eng.kv
    assert kv.v is None and len(kv.pools) == 2
    assert kv.k.shape == (3, 48 * 4, 16 + 8)
    assert kv.idx.shape == (3, 48 * 4 // 2, 2 * 16)
    per_token = 3 * (24 + 16) * 4        # layers x (latent row + key) x f32
    assert kv_pool_stats(kv, eng.allocator)["pool_bytes"] == per_token * 192
    assert kv.with_pools([p + 1 for p in kv.pools]).idx.min() == 1
    assert dsa.pool_views(built[0].config, kv.pools) == (
        kv.k, None, 16, kv.idx)


def test_indexer_keys_are_written_and_read_beside_a_latent_pool(built):
    """``index_pool_write`` / ``seq_index_keys`` on the second array of a
    latent pool: keys written at odd and even slots through a block table
    come back in position order, a pair of mates as one row."""
    cfg = built[0].config
    pool = jnp.zeros((3, 24, 2 * cfg.index_head_dim))
    k_i = jnp.asarray(np.random.default_rng(1).standard_normal(
        (5, cfg.index_head_dim)), jnp.float32)
    # a sequence in blocks 3 and 1 (block_size 4): positions 2..6
    tables = jnp.asarray([[3, 1, 0]])
    pos = jnp.arange(2, 7)
    dest = tables[0, pos // 4] * 4 + pos % 4
    mates = dsa.pair_mates(jnp.zeros((5,), jnp.int32), pos,
                           jnp.ones((5,), bool))
    assert mates.tolist() == [1, 0, 3, 2, -1]
    pool = dsa.index_pool_write(pool, 1, dest, k_i, mates)
    keys = dsa.seq_index_keys(pool, 1, tables, 4)
    assert keys.shape == (1, 12, cfg.index_head_dim)
    np.testing.assert_array_equal(keys[0, 2:7], k_i)
    assert not np.asarray(keys[0, :2]).any() and not np.asarray(pool[0]).any()


def test_index_rows_read_the_query_latent_and_rotate_half_a_head(built):
    """``dsa.index_rows``: the queries are a projection of ``c_q`` (a change
    of ``y`` alone moves keys and weights, not them), and of a head's 16
    dims the last 8 do not depend on the position."""
    from deepspeedsyclsupport_tpu.inference.v2.model import serving_layout

    cfg = built[0].config
    p = jax.tree_util.tree_map(
        lambda w: w[0], serving_layout(built[1], cfg)["layers"]["attn"])
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    c_q = jnp.asarray(rng.standard_normal((3, 24)), jnp.float32)
    pos = jnp.asarray([5, 9, 30])
    q_i, k_i, w = dsa.index_rows(p, (y, c_q), cfg, pos)
    assert (q_i.shape, k_i.shape, w.shape) == ((3, 2, 16), (3, 16), (3, 2))
    q_2, k_2, w_2 = dsa.index_rows(p, (2 * y, c_q), cfg, pos)
    np.testing.assert_array_equal(q_2, q_i)
    assert float(jnp.abs(w_2 - w).max()) > 0
    q_0, k_0, _ = dsa.index_rows(p, (y, c_q), cfg, jnp.zeros_like(pos))
    np.testing.assert_array_equal(q_0[..., 8:], q_i[..., 8:])
    np.testing.assert_array_equal(k_0[..., 8:], k_i[..., 8:])
    assert float(jnp.abs(q_0[..., :8] - q_i[..., :8]).max()) > 1e-3
