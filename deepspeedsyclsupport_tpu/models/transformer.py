"""Flagship causal-LM transformer (Llama/Mistral/Mixtral family), TPU-first.

This replaces the reference's model-integration machinery — policy-driven module
surgery (``deepspeed/module_inject/replace_module.py:182``), per-arch containers
(``module_inject/containers/*``), and the inference-v2 model zoo
(``inference/v2/model_implementations/``) — with a framework-owned functional model:

* params are a plain pytree (stacked per-layer leaves, leading dim = layer) so the
  whole depth compiles as ONE ``lax.scan`` step — constant compile time in depth,
  and ZeRO/TP placement is just sharding rules over the stacked leaves.
* the same ``_forward`` serves training (no cache) and decode (KV cache carried
  through the scan) — the train/generate weight-sharing the reference needs a
  whole Hybrid Engine for (``runtime/hybrid_engine.py:32``).
* tensor-parallel layout is declared, not rewritten: :meth:`sharding_rules` gives
  Megatron-style specs (the auto-TP analog of ``module_inject/auto_tp.py:483``)
  that ``runtime/zero.py`` composes with FSDP placement.
"""
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import remat
from .config import ModelConfig, get_config
from .layers import (BATCH, _manual_axes, attention_block, constrain,
                     mlp_block, norm)

Params = Dict[str, Any]

# How init_params SEEDS the scales of a sandwich_norm stack's post-sublayer
# norms (post_norm_params): what the attention sublayers and the MLPs of one
# walk of the stack each write together, as a share of a unit residual
# stream, and the spread of the log-normal draw over a scale's channels. By
# sweeps on the v5e against the float32 reference (PERF.md section 6, PR 39;
# benchmark/configs/ouro-2.6b.json, assumed.weights): a bf16 stream is
# rounded at every write, 384 times a token at 48 layers x 4 passes, and that
# rounding, not any sublayer's arithmetic, is what the served logits differ
# by. A checkpoint overwrites all of it.
POST_NORM_WRITE = (3.0, 0.5)
POST_NORM_SPREAD = 2.0
# How init_params SEEDS the output projection of an attention that reads a
# SELECTION of its keys (ModelConfig.index_topk), as a share of the rule for
# every other ``wo``. Over seeded values the softmax over the selected keys
# is flat, so each of the ~1 % of a row's keys that bf16 moves across its
# index_topk-th score carries a whole 1 / index_topk of the output, where a
# trained indexer's marginal keys carry next to nothing: the seeded sublayer
# is as discontinuous as a seeded router (routed_write_share is the same
# rule for the experts behind one). By a sweep on the v5e against the
# float32 reference (PERF.md section 6, PR 45): the worst of 256 rows at
# 41 k of context read 0.099-0.116 logit-std at 1, 0.064 at 1/2, 0.055 at
# 1/4, of which 0.052 moves with no share (the bf16 stream itself). A rule
# of the draw, not an option: a checkpoint overwrites it.
SELECTED_ATTN_WRITE = 0.5
# ... and where the selection lies INSIDE latent attention (kv_lora_rank with
# index_topk), whose ``wo`` is drawn by ONE head's fan-in over 64 heads: by a
# sweep on the v5e (PERF.md section 6, PR 65; the 8.8 k probe, routed experts
# mute): with the attention's write at nothing the median of 141 rows read
# 0.043 logit-std, with every key selected 0.055 (the bf16 stream rounds at
# every residual add that adds something), at 1/2 0.081 (worst row 0.102):
# 0.059 in quadrature that follows the share, from 6 of layer 0's 2,048
# selected keys that bf16 moves across the threshold and more in the layers
# behind it, each a whole key of a flat softmax. At 1/4 a wrong selection
# still reads several times the limit.
SELECTED_LATENT_WRITE = 0.25
# How init_params SEEDS the scale of latent attention's query-latent norm
# where a sparse-attention indexer's queries read that latent
# (ModelConfig.index_q_latent): log-normal a channel with this sigma, at unit
# root mean square. At a scale of ones the norm is a positive factor a ROW,
# which moves no row's selection: a program whose indexer read the latent
# BEFORE its norm would select the same keys and pass for right
# (tools/glm5_faults.py plants it); at sigma 1 the two rows' scores
# correlate at exp(-1/2) = 0.61. A checkpoint overwrites it.
INDEX_Q_NORM_SPREAD = 1.0


class KVCache(NamedTuple):
    """Per-model decode cache: stacked [L, B, max_len, kv_heads, head_dim]."""
    k: jnp.ndarray
    v: jnp.ndarray
    write_pos: jnp.ndarray  # scalar int32: next slot to fill


class CausalLM:
    """Decoder-only LM implementing the engine protocol:
    ``init_params() -> pytree``, ``loss(params, batch, rng) -> (loss, metrics)``,
    ``sharding_rules(path, shape) -> PartitionSpec prefix``.
    """

    def __init__(self, config: ModelConfig, seed: int = 0):
        self.config = config
        self.seed = seed

    # ------------------------------------------------------------------ init
    def init_params(self, rng: Optional[jax.Array] = None) -> Params:
        cfg = self.config
        rng = rng if rng is not None else jax.random.PRNGKey(self.seed)
        std = cfg.initializer_range
        # a matrix whose product a muP multiplier follows (or whose input one
        # scales) is drawn THROUGH it, std / multiplier: the multipliers
        # presume muP's larger matrices, and at N(0, 0.02) for every leaf
        # falcon_h1's attention logits would be flat (q k^T / sqrt(128) ~
        # 0.02) and its three branches write 1-4 % of what the embedding
        # does (all 1.0, and nothing changes, for every other family)
        mup = cfg.mup
        keys = iter(jax.random.split(rng, 64))

        def dense(shape, key, scale=std):
            return (jax.random.normal(key, shape, jnp.float32) * scale)

        def down_scale(fan_in):
            """Std of a projection back into the stream: GPT-2's depth
            scaling, a rule of training; the latent-attention families,
            which only serve, draw it by fan-in, so that each sublayer
            writes at about the embedding's size and a comparison of logits
            weighs attention, MLP and embedding alike (where hyper-
            connection maps gate every write themselves, ``H_post`` ~ 1)."""
            if cfg.layer_pattern:
                # a hybrid stack has ONE mixer a layer and is cut less deep
                # than the latent families: all its mixers TOGETHER write
                # about what the embedding does. At the embedding's size
                # EACH, 26 layers' bf16 rounding compounds to 0.065
                # logit-std at the median row (PERF.md section 6, PR 37)
                # (under muP's scalings the embedding is embed_scale times
                # as large and a sublayer's write residual_scale times as
                # small: the draw keeps the two in that proportion)
                return std * cfg.embed_scale / cfg.residual_scale \
                    / np.sqrt(fan_in * cfg.num_layers)
            if cfg.retention_degree:
                # the same rule for a power-retention stack, which only
                # serves: at GPT-2's depth scaling each sublayer writes ~25
                # times what the embedding does, every layer's input is the
                # rounded output of the layers before it, and the bf16
                # roundings of 16 sublayers compound to 0.10 logit-std at
                # the MEDIAN row on the v5e (PERF.md section 6, PR 49)
                return std / np.sqrt(fan_in * cfg.num_layers)
            if cfg.kv_lora_rank:
                return std / np.sqrt(fan_in)
            return std / np.sqrt(2 * cfg.num_layers)

        def norm_params() -> Params:
            p = {"scale": jnp.ones((cfg.hidden_size,), jnp.float32)}
            if cfg.norm_type == "layernorm" and cfg.norm_bias:
                p["bias"] = jnp.zeros((cfg.hidden_size,), jnp.float32)
            return p

        def post_norm_params(key, write) -> Params:
            """The norm over a sublayer's OUTPUT (``cfg.sandwich_norm``):
            its scale IS the size of the sublayer's write into the stream,
            whatever the projections draw. Seeded log-normal a channel
            (:data:`POST_NORM_SPREAD`) around ``write / sqrt(2 L)`` in the
            root mean square: the sublayers of one kind in ONE walk of the
            stack together write ``write`` times a unit stream (what the
            final norm hands the next pass of a looped stack). A checkpoint
            overwrites it."""
            z = jnp.exp(POST_NORM_SPREAD * jax.random.normal(
                key, (cfg.hidden_size,), jnp.float32))
            return {"scale": z * jax.lax.rsqrt(jnp.mean(z * z)) * (
                write / np.sqrt(2 * cfg.num_layers))}

        def index_params(ks) -> Params:
            """The sparse-attention indexer's leaves (none without
            ``cfg.index_topk``): index_heads small query heads, ONE key a
            token (behind a LayerNorm) and a weight a head. Key and weights
            come of the row q reads; the queries too, or
            (``cfg.index_q_latent``) of latent attention's query latent."""
            if not cfg.index_topk:
                return {}
            d, hi, di = cfg.hidden_size, cfg.index_heads, cfg.index_head_dim
            q_in = cfg.q_lora_rank if cfg.index_q_latent else d
            return {
                "w_qi": dense((q_in, hi * di), next(ks)),
                "w_ki": dense((d, di), next(ks)),
                "ki_norm": {"scale": jnp.ones((di,), jnp.float32),
                            "bias": jnp.zeros((di,), jnp.float32)},
                "w_w": dense((d, hi), next(ks))}

        def attn_params(ks) -> Params:
            d, q, kv = cfg.hidden_size, cfg.q_dim, cfg.kv_dim
            if cfg.kv_lora_rank:
                # latent attention: q and kv go down to a normed latent and
                # up again; w_kva also gives the one rotated key all heads
                # share, w_kvb each head's un-rotated key and its value
                h, r = cfg.num_heads, cfg.kv_lora_rank
                k_qa = next(ks)
                q_scale = jnp.ones((cfg.q_lora_rank,), jnp.float32)
                if cfg.index_q_latent:    # INDEX_Q_NORM_SPREAD has why
                    z = jnp.exp(INDEX_Q_NORM_SPREAD * jax.random.normal(
                        jax.random.fold_in(k_qa, 1), q_scale.shape,
                        jnp.float32))
                    q_scale = z * jax.lax.rsqrt(jnp.mean(z * z))
                return {
                    "w_qa": dense((d, cfg.q_lora_rank), k_qa),
                    "q_norm": {"scale": q_scale},
                    "w_qb": dense((cfg.q_lora_rank, h * (
                        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
                        next(ks)),
                    "w_kva": dense((d, cfg.latent_kv_dim), next(ks)),
                    "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
                    "w_kvb": dense((r, h * (cfg.qk_nope_head_dim
                                            + cfg.v_head_dim)), next(ks)),
                    # by ONE head's fan-in: what it projects is a mean of
                    # values over the tokens attended, small already
                    "wo": dense((h * cfg.v_head_dim, d), next(ks),
                                scale=down_scale(cfg.v_head_dim) * (
                                    SELECTED_LATENT_WRITE if cfg.index_topk
                                    else 1.0)),
                    **index_params(ks),
                }
            into = std / mup.attention_in
            attn: Params = {
                "wq": dense((d, q), next(ks), scale=into),
                "wk": dense((d, kv), next(ks), scale=into / mup.key),
                "wv": dense((d, kv), next(ks), scale=into),
                "wo": dense((q, d), next(ks), scale=down_scale(q) * (
                    SELECTED_ATTN_WRITE if cfg.index_topk else 1.0)
                    / mup.attention_out),
            }
            if cfg.qkv_bias:
                attn.update(bq=jnp.zeros((q,), jnp.float32),
                            bk=jnp.zeros((kv,), jnp.float32),
                            bv=jnp.zeros((kv,), jnp.float32))
            if cfg.attn_out_bias:
                attn["bo"] = jnp.zeros((d,), jnp.float32)
            if cfg.qk_norm:
                attn.update(q_norm={"scale": jnp.ones((q,), jnp.float32)},
                            k_norm={"scale": jnp.ones((kv,), jnp.float32)})
            if cfg.qk_head_norm:
                hd = cfg.head_dim
                attn.update(q_norm={"scale": jnp.ones((hd,), jnp.float32)},
                            k_norm={"scale": jnp.ones((hd,), jnp.float32)})
            if cfg.retention_degree:
                # power retention's gate, one a KV head: log sigmoid(y
                # g_proj + g_bias) is what a head's state decays by a token.
                # The bias is drawn so that a head's half-life is
                # log-uniform over cfg.retention_half_life tokens (sigmoid(b)
                # = 2^(-1 / half-life)): with N(0, 0.02) alone every gate is
                # one half, the state forgets in ten tokens and a program
                # that carried nothing between pieces would pass for right.
                # A checkpoint overwrites it.
                lo, hi = cfg.retention_half_life
                life = jnp.exp(jax.random.uniform(
                    next(ks), (cfg.num_kv_heads,), jnp.float32,
                    np.log(lo), np.log(hi)))
                keep = jnp.exp2(-1.0 / life)
                attn.update(g_proj=dense((d, cfg.num_kv_heads), next(ks)),
                            g_bias=jnp.log(keep) - jnp.log1p(-keep))
            if cfg.attn_out_gate:
                # the output gate: sigmoid(y w_g) over all q_dim values
                attn["w_g"] = dense((d, q), next(ks))
            attn.update(index_params(ks))
            return attn

        def glu_params(ks, f) -> Params:
            d = cfg.hidden_size
            return {"w_gate": dense((d, f), next(ks),
                                    scale=std / mup.mlp[0]),
                    "w_up": dense((d, f), next(ks)),
                    "w_down": dense((f, d), next(ks),
                                    scale=down_scale(f) / mup.mlp[1])}

        def mlp_params(ks, f) -> Params:
            d = cfg.hidden_size
            return {"fc1": dense((d, f), next(ks)),
                    "fc2": dense((f, d), next(ks), scale=down_scale(f))}

        def step_bias(key, n):
            """``n`` steps log-uniform in ``time_step_min..max`` (floored at
            ``time_step_floor``), kept as the inverse softplus: Mamba-2's
            published draw of ``dt_bias``, the delta rule's too."""
            dt = jnp.exp(jax.random.uniform(key, (n,), jnp.float32)
                         * (np.log(cfg.time_step_max)
                            - np.log(cfg.time_step_min))
                         + np.log(cfg.time_step_min))
            dt = jnp.maximum(dt, cfg.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))

        def mamba_params(key) -> Params:
            """One Mamba-2 mixer behind its norm: ``in_proj`` to ``[z | xBC
            | dt]``, the depthwise convolution ``[kernel, channels]`` with
            its bias, and per head ``A_log``, ``dt_bias`` and ``D`` over the
            published init's range: A uniform in 1..16, dt log-uniform in
            ``time_step_min..max`` (kept as the inverse softplus), D one."""
            ks = iter(jax.random.split(key, 8))
            d, di, h = cfg.hidden_size, cfg.ssm_d_inner, cfg.mamba_num_heads
            c, kw = cfg.ssm_conv_dim, cfg.ssm_conv_kernel
            bound = 1.0 / np.sqrt(kw)    # a depthwise conv's fan-in
            dt_bias = step_bias(next(ks), h)
            p = {
                "norm": norm_params(),
                "in_proj": dense((d, di + c + h), next(ks)),
                "conv_w": jax.random.uniform(next(ks), (kw, c), jnp.float32,
                                             -bound, bound),
                "conv_b": jax.random.uniform(next(ks), (c,), jnp.float32,
                                             -bound, bound),
                "A_log": jnp.log(jax.random.uniform(
                    next(ks), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt_bias,
                "D": jnp.ones((h,), jnp.float32),
                "gate_norm": {"scale": jnp.ones((di,), jnp.float32)},
                "out_proj": dense((di, d), next(ks),
                                  scale=down_scale(di) / mup.ssm_out),
            }
            if cfg.mup_in_proj is not None:    # through ssm_in and ssm's
                p["in_proj"] = p["in_proj"] / cfg.mup_in_proj
            return p

        def hybrid_params(key) -> Params:
            """An ``H`` layer: ONE norm, attention heads and a Mamba-2 mixer
            that both read it."""
            k_attn, k_mamba = jax.random.split(key)
            mamba = mamba_params(k_mamba)
            return {"norm": mamba.pop("norm"),
                    "attn": attn_params(iter(jax.random.split(k_attn, 16))),
                    "mamba": mamba}

        def kda_params(key) -> Params:
            """One gated delta-rule mixer behind its norm (``ops/kda.py``):
            ``qkv_proj`` to ``[q | k | v]``, ONE depthwise convolution
            ``[kernel, 3 x heads x dim]`` over them (no bias); the decay's
            pair ``f_a`` / ``f_b`` with ``A_log`` a head and ``dt_bias`` a
            key channel, drawn as Mamba-2's (A uniform in 1..16, the step
            log-uniform in ``time_step_min..max``, kept as the inverse
            softplus: half-lives from under a token to some hundreds);
            ``b_proj`` the write's strength a head; the output gate's pair
            ``g_a`` / ``g_b``, the norm a head ``o_norm`` and ``o_proj``."""
            ks = iter(jax.random.split(key, 12))
            d, h, dk = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_dim
            r, kw = cfg.kda_gate_rank, cfg.kda_conv_kernel
            bound = 1.0 / np.sqrt(kw)    # a depthwise conv's fan-in
            dt_bias = step_bias(next(ks), dk)
            return {
                "norm": norm_params(),
                "qkv_proj": dense((d, 3 * dk), next(ks)),
                "conv_w": jax.random.uniform(next(ks), (kw, 3 * dk),
                                             jnp.float32, -bound, bound),
                "f_a": dense((d, r), next(ks)),
                "f_b": dense((r, dk), next(ks)),
                "A_log": jnp.log(jax.random.uniform(
                    next(ks), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt_bias,
                "b_proj": dense((d, h), next(ks)),
                "g_a": dense((d, r), next(ks)),
                "g_b": dense((r, dk), next(ks)),
                "o_norm": {"scale": jnp.ones((cfg.kda_head_dim,),
                                             jnp.float32)},
                "o_proj": dense((dk, d), next(ks), scale=down_scale(dk)),
            }

        def lightning_params(key) -> Params:
            """One lightning linear-attention mixer behind its norm
            (``ops/ssm.py``'s convolution-free entries): ``wq`` / ``wk`` /
            ``wv`` to heads of ``lightning_head_dim``, the norms a head of q
            and k, the output gate ``wz``, the norm over all the heads'
            outputs ``o_norm`` and ``wo``. The decay is no leaf: a constant
            a head (``ops.ssm.lightning_decay``)."""
            ks = iter(jax.random.split(key, 8))
            d, dl, hd = (cfg.hidden_size, cfg.lightning_dim,
                         cfg.lightning_head_dim)
            return {
                "norm": norm_params(),
                "wq": dense((d, dl), next(ks)),
                "wk": dense((d, dl), next(ks)),
                "wv": dense((d, dl), next(ks)),
                "wz": dense((d, dl), next(ks)),
                "q_norm": {"scale": jnp.ones((hd,), jnp.float32)},
                "k_norm": {"scale": jnp.ones((hd,), jnp.float32)},
                "o_norm": {"scale": jnp.ones((dl,), jnp.float32)},
                "wo": dense((dl, d), next(ks), scale=down_scale(dl)),
            }

        def ffn_params(key) -> Params:
            """A dense feed-forward part alone behind its norm (``F``)."""
            return {"mlp_norm": norm_params(),
                    "mlp": glu_params(iter(jax.random.split(key, 3)),
                                      cfg.intermediate_size)}

        def hc_params(key) -> Params:
            """One sublayer's hyper-connection maps: the norm over all
            ``n * d`` stream values, ``phi`` [n*d, n + n + n*n] (columns
            pre | post | res), the three gains ``a`` and the biases ``b``:
            unit noise for pre and post (``H_post`` about 1), and for res
            twice the identity plus half-unit noise, so that the Sinkhorn
            matrix leans to the identity and is neither it nor uniform."""
            n, (k1, k2) = cfg.hc_mult, jax.random.split(key)
            noise = jax.random.normal(k2, (2 * n + n * n,), jnp.float32)
            return {"norm": {"scale": jnp.ones((n * cfg.hidden_size,),
                                               jnp.float32)},
                    "phi": dense((n * cfg.hidden_size, 2 * n + n * n), k1),
                    "a": jnp.asarray([0.5, 0.1, 0.5], jnp.float32),
                    "b": jnp.concatenate([
                        noise[:2 * n],
                        2.0 * jnp.eye(n).reshape(-1) + 0.5 * noise[2 * n:]])}

        def layer_params(key, moe=cfg.any_moe, kind=None) -> Params:
            """One layer; ``kind`` ('E' or '*') of a ``layer_pattern``
            model: the one mixer behind its one norm."""
            ks = iter(jax.random.split(key, 16))
            d, f = cfg.hidden_size, cfg.intermediate_size
            if kind == "*":
                return {"attn_norm": norm_params(), "attn": attn_params(ks)}
            p: Params = {} if kind else {"attn_norm": norm_params(),
                                         "attn": attn_params(ks)}
            if not cfg.shared_block_norm:
                p["mlp_norm"] = norm_params()
            if cfg.sandwich_norm:
                p["attn_post_norm"] = post_norm_params(
                    next(ks), POST_NORM_WRITE[0])
                p["mlp_post_norm"] = post_norm_params(
                    next(ks), POST_NORM_WRITE[1])
            if cfg.hc_mult > 1:
                p["hc_attn"] = hc_params(next(ks))
                p["hc_mlp"] = hc_params(next(ks))
            if moe:
                # the router over all the experts, the matrices of those
                # held here (all, unless this is an expert-parallel share)
                e, held = cfg.num_experts, cfg.experts_held
                fe = cfg.moe_intermediate_size or f
                # beside a shared expert the routed ones share the write:
                # each is drawn at 1 / E of the shared one's size, or at
                # the share the preset gives
                down = down_scale(fe) / (e if cfg.n_shared_experts else 1)
                if cfg.routed_write_share is not None:
                    down = down_scale(fe) * cfg.routed_write_share
                glu = cfg.mlp_type == "glu"
                # stored wider than drawn, zeros beyond the width
                # (ModelConfig.expert_width_stored)
                wide = cfg.expert_width_stored - fe
                cols = lambda w: jnp.pad(  # noqa: E731
                    w, ((0, 0), (0, 0), (0, wide))) if wide else w
                p["moe"] = {
                    "router": dense((d, e), next(ks)),
                    # a two-matrix expert (mlp_type "mlp") has no gate
                    **({"w_gate": cols(dense((held, d, fe), next(ks)))}
                       if glu else {}),
                    "w_up": cols(dense((held, d, fe), next(ks))),
                    "w_down": dense((held, fe, d), next(ks), scale=down),
                }
                if wide:
                    p["moe"]["w_down"] = jnp.pad(
                        p["moe"]["w_down"], ((0, 0), (0, wide), (0, 0)))
                if cfg.topk_method == "noaux_tc":
                    # the selection-only bias
                    p["moe"]["router_bias"] = dense((e,), next(ks))
                if cfg.n_shared_experts:
                    p["moe"]["shared"] = (glu_params if glu else mlp_params)(
                        ks, cfg.shared_expert_width)
            elif cfg.mlp_type == "mlp":
                p["mlp"] = {
                    "fc1": dense((d, f), next(ks)),
                    "fc2": dense((f, d), next(ks),
                                 scale=std / np.sqrt(2 * cfg.num_layers)),
                }
                if cfg.use_bias:
                    p["mlp"].update(b1=jnp.zeros((f,), jnp.float32),
                                    b2=jnp.zeros((d,), jnp.float32))
            else:
                p["mlp"] = glu_params(ks, f)
            return p

        n_dense = cfg.first_k_dense_replace
        stacks: Params = {}
        if cfg.layer_pattern is not None:
            # a stack a kind, each in the order its layers come in the pattern
            lkeys = jax.random.split(next(keys), cfg.num_layers)
            of = lambda kind: lkeys[np.asarray(  # noqa: E731
                [i for i, c in enumerate(cfg.layer_pattern) if c == kind],
                np.int32)]
            layers = jax.vmap(lambda k: layer_params(k, kind="E"))(of("E")) \
                if cfg.pattern_count("E") else {}
            if cfg.pattern_count("M"):
                stacks["mamba_layers"] = jax.vmap(mamba_params)(of("M"))
            if cfg.pattern_count("H"):
                stacks["hybrid_layers"] = jax.vmap(hybrid_params)(of("H"))
            if cfg.pattern_count("K"):
                stacks["kda_layers"] = jax.vmap(kda_params)(of("K"))
            if cfg.pattern_count("L"):
                stacks["lightning_layers"] = jax.vmap(lightning_params)(
                    of("L"))
            if cfg.pattern_count("F"):
                stacks["ffn_layers"] = jax.vmap(ffn_params)(of("F"))
            if cfg.pattern_count("*"):
                stacks["attn_layers"] = jax.vmap(
                    lambda k: layer_params(k, kind="*"))(of("*"))
        elif cfg.scan_layers:
            lkeys = jax.random.split(next(keys), cfg.num_layers)
            # stacked leaves [L, ...]; the leading dense layers of a
            # first_k_dense_replace model are a stack of their own
            layers = jax.vmap(layer_params)(lkeys[n_dense:])
        else:
            layers = [layer_params(k)
                      for k in jax.random.split(next(keys), cfg.num_layers)]
        params: Params = {
            "embed": {"embedding": dense((cfg.vocab_size, cfg.hidden_size),
                                         next(keys))},
            "layers": layers,
            **stacks,
            "final_norm": norm_params(),
        }
        if n_dense:
            params["dense_layers"] = jax.vmap(
                lambda k: layer_params(k, moe=False))(lkeys[:n_dense])
        if cfg.pos_embed == "learned":
            # OPT-style tables carry pos_embed_offset extra rows and are
            # indexed at position + offset (HF OPTLearnedPositionalEmbedding)
            params["pos_embed"] = {"embedding": dense(
                (cfg.max_seq_len + cfg.pos_embed_offset, cfg.hidden_size),
                next(keys))}
        if cfg.embed_norm:
            params["embed_norm"] = norm_params()
        if cfg.total_ut_steps > 1:
            # the exit gate: one number a token and pass from the pass's
            # normed output (sigmoid of it: the share that leaves here)
            params["exit_gate"] = {
                "kernel": dense((cfg.hidden_size, 1), next(keys)),
                "bias": jnp.zeros((1,), jnp.float32)}
        if not cfg.tie_embeddings:
            params["lm_head"] = {
                "kernel": dense((cfg.hidden_size, cfg.vocab_size), next(keys))}
            if cfg.lm_head_bias:
                params["lm_head"]["bias"] = jnp.zeros((cfg.vocab_size,),
                                                      jnp.float32)
        return params

    # ------------------------------------------------------------------ forward
    def _layer(self, p: Params, x: jnp.ndarray, positions, segment_ids,
               cache_slice, rng, kv_mask=None, kv_positions=None,
               layer_idx: Optional[int] = None
               ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        cfg = self.config
        # ZeRO-Inference: int8 QuantTensor leaves dequantize here, inside the
        # layer scan — at most one layer's weights are fp at a time
        from ..compression.quantize import dequantize_tree

        p = dequantize_tree(p, jnp.dtype(cfg.dtype))
        dtype = x.dtype  # pin activation dtype: fp32 params must not promote bf16

        def run_mlp(y):
            if cfg.any_moe:
                from ..monitor.mfu import region_scope
                from ..parallel.moe import moe_mlp

                with region_scope("mlp"):  # MoE is the mlp MFU region too
                    return moe_mlp(p["moe"], y, cfg, rng)
            return mlp_block(p["mlp"], y, cfg), jnp.zeros((), jnp.float32)

        from .layers import _WINDOW_FROM_CFG

        window = (cfg.attn_windows[layer_idx]
                  if cfg.attn_windows is not None and layer_idx is not None
                  else _WINDOW_FROM_CFG)
        x_norm = norm(x, p["attn_norm"], cfg)
        h, new_cache = attention_block(
            p["attn"], x_norm, cfg, positions, segment_ids, cache_slice,
            kv_mask=kv_mask, kv_positions=kv_positions,
            window_override=window)
        if cfg.parallel_block:
            # GPT-J/NeoX/Falcon/Phi residual form: x + attn(norm(x)) + mlp(·),
            # with the MLP reading either the same norm (shared_block_norm)
            # or its own norm of the SAME input x (NeoX two-norm form)
            y = x_norm if cfg.shared_block_norm else norm(x, p["mlp_norm"], cfg)
            m, aux = run_mlp(y)
            return (x + h + m).astype(dtype), new_cache, aux
        x = (x + h).astype(dtype)
        h, aux = run_mlp(norm(x, p["mlp_norm"], cfg))
        return (x + h).astype(dtype), new_cache, aux

    def _remat_policy(self, b: int, s: int, note: bool = True):
        """The ``jax.checkpoint`` policy of a checkpointed layer (None:
        nothing saved) from ``cfg.remat_policy``: a ``jax.checkpoint_policies``
        name means what it says; a rung of ``models/remat.py`` keeps the
        tensors it names; ``"auto"`` (what the engine passes when its
        ``activation_checkpointing`` section names no policy) takes the
        richest rung whose saved bytes, over all layers, fit in
        ``cfg.remat_free_bytes``, the device's memory less the engine's
        resident state: ``nothing_saveable`` where no limit is known. The
        shapes are the trace's: ``b`` x ``s`` tokens and the widths, over
        the mesh axes that split them and are not manual here (inside a
        ``shard_map`` the trace sees a shard's own). What a TRAINING trace
        took (``note``) is left in ``self.remat_choice`` for the engine to
        log and publish. Two
        stacks never come here and checkpoint WHOLE layers under any policy:
        the pipelined trunk (``spmd_pipeline(remat=cfg.remat)``) and
        random-LTD's middle stack (a bare ``jax.checkpoint``)."""
        cfg = self.config
        name = cfg.remat_policy
        if name == "offload_dots_to_host":
            # activation offload (reference cpu_checkpointing): saved
            # dots land in pinned host memory instead of HBM
            return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                "device", "pinned_host")
        if name != "auto" and name not in remat.RUNGS:
            return getattr(jax.checkpoint_policies, name) if name else None
        from ..comm import topology as topo_mod

        topo = topo_mod._WORLD_TOPOLOGY
        sizes = topo.axis_sizes if topo is not None else {}
        manual = _manual_axes()

        def shards(*axes):
            return int(np.prod([sizes.get(a, 1) for a in axes
                                if a not in manual]))

        tokens = b * s / shards(*BATCH, "seq")
        split = (shards("model"), shards("expert"))
        rung = name if name != "auto" else remat.choose_rung(
            cfg, tokens, cfg.remat_free_bytes, *split)
        saved = remat.rung_bytes(cfg, tokens, *split)[rung] * cfg.num_layers
        if note:
            self.remat_choice = {"rung": rung, "saved_bytes": saved}
        return remat.rung_policy(rung)

    def _forward(self, params: Params, input_ids: jnp.ndarray,
                 positions: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None,
                 cache: Optional[KVCache] = None,
                 rng: Optional[jax.Array] = None,
                 kv_mask: Optional[jnp.ndarray] = None,
                 kv_positions: Optional[jnp.ndarray] = None,
                 pld_theta: Optional[jnp.ndarray] = None,
                 train: bool = True
                 ) -> Tuple[jnp.ndarray, Optional[KVCache], jnp.ndarray]:
        """Returns (logits [B,S,V] fp32, new_cache, total_aux_loss)."""
        cfg = self.config
        if cfg.total_ut_steps > 1 or cfg.sandwich_norm:
            raise NotImplementedError(
                "a looped stack (total_ut_steps > 1: the layers run several "
                "times over shared weights, an exit gate after each pass) "
                "and post-sublayer norms (sandwich_norm) run on the serving "
                "path only (inference/v2/model.py): the gradients of weights "
                "used several times and the loss over the exit distribution "
                "are not written")
        if cfg.layer_pattern is not None:
            raise NotImplementedError(
                "a layer_pattern model (Mamba-2, gated delta-rule, "
                "lightning, expert, feed-forward and attention layers in "
                "one stack; block-sparse attention, sparse_block_topk) "
                "runs on the serving path only (inference/v2/model.py): "
                "the chunked scan's backward is not written")
        if cfg.retention_degree:
            raise NotImplementedError(
                "a power-retention model (retention_degree: a gated state "
                "a sequence, layer and KV head in the place of attention) "
                "runs on the serving path only (inference/v2/model.py, "
                "ops/retention.py): the chunked form's backward is not "
                "written")
        if cfg.kv_lora_rank or cfg.hc_mult > 1 or cfg.first_k_dense_replace \
                or cfg.experts_held != cfg.num_experts or cfg.index_topk \
                or cfg.topk_method == "group_limited_greedy" \
                or cfg.attn_period is not None \
                or cfg.shared_expert_combine != "sum":
            raise NotImplementedError(
                "latent attention, hyper-connection streams, leading dense "
                "layers, group-limited routing, a share of the experts, "
                "the sparse-attention indexer (index_topk, over K and V or "
                "over a latent pool), a period of "
                "attention kinds (attn_period) and averaged shared experts "
                "run on the serving path only "
                "(inference/v2/model.py): their training forward and "
                "backward are not written")
        b, s = input_ids.shape
        if positions is None:
            base = cache.write_pos if cache is not None else 0
            positions = jnp.arange(s)[None, :] + base
            positions = jnp.broadcast_to(positions, (b, s))
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        from ..monitor.mfu import region_scope
        from ..parallel.tensor_parallel import vocab_parallel_embedding

        with region_scope("embed"):  # MFU-region label (monitor/mfu.py)
            x = vocab_parallel_embedding(params["embed"]["embedding"],
                                         input_ids)
            if cfg.pos_embed == "learned":
                # same Megatron masked-lookup+psum pattern as the vocab
                # table — a plain take on a row-sharded table makes SPMD
                # full-remat
                table = params["pos_embed"]["embedding"]
                pos = jnp.clip(positions + cfg.pos_embed_offset, 0,
                               table.shape[0] - 1)
                x = x + vocab_parallel_embedding(table, pos).astype(x.dtype)
            x = x.astype(jnp.dtype(cfg.dtype))
            if cfg.embed_norm:
                x = norm(x, params["embed_norm"], cfg)
            x = constrain(x, BATCH, "seq", None)

        def layer_fn(x, p, ck, cv, rng_l, layer_idx=None):
            cache_slice = None
            if cache is not None:
                cache_slice = (ck, cv, cache.write_pos)
            x, new_c, aux = self._layer(p, x, positions, segment_ids,
                                        cache_slice, rng_l, kv_mask=kv_mask,
                                        kv_positions=kv_positions,
                                        layer_idx=layer_idx)
            nck, ncv = (new_c[0], new_c[1]) if new_c is not None else (ck, cv)
            return x, nck, ncv, aux

        new_cache = None
        rltd_keep = cfg.random_ltd_current
        use_rltd = (cfg.random_ltd and train and cache is None
                    and cfg.scan_layers and rltd_keep is not None
                    and rltd_keep < s and cfg.num_layers >= 3)
        from ..comm import topology as topo_mod

        wtopo = topo_mod._WORLD_TOPOLOGY
        # cfg.pipe_stages (set by the engine from its topology) decides the
        # trunk explicitly; the world-topology read is the fallback for
        # direct model.loss() use. NOTE: the fallback is read at TRACE time —
        # a jitted callable keeps the topology live at its first trace.
        if cfg.pipe_stages is not None:
            pipe_n = cfg.pipe_stages
        else:
            pipe_n = wtopo.axis_sizes.get("pipe", 1) if wtopo is not None else 1
        if cfg.remat and pipe_n == 1 and not use_rltd:
            # layer_idx is a STATIC python arg (per-layer window selection).
            # The pipelined trunk and random-LTD's middle stack below take
            # the bare flag: whole-layer remat, whatever the policy.
            # Under the scan the forward and the backward are two loops and
            # nothing can merge the recomputation into the forward, so the
            # barriers that prevent it are left out: they held every saved
            # tensor's slice of the layer live at once (15.18 GiB against
            # 14.77 for mistral-7b-d2's step under attn+mlp: PERF.md, PR 43)
            layer_fn = jax.checkpoint(
                layer_fn, policy=self._remat_policy(b, s, note=train),
                prevent_cse=not cfg.scan_layers, static_argnums=(5,))
        if pipe_n > 1:
            # Pipeline-parallel trunk (reference ``runtime/pipe/module.py:636``
            # PipelineModule semantics, reachable from ``{"pipeline":
            # {"stages": N}}``): embed/head stay outside the pipeline (the
            # TiedLayerSpec pattern), the stacked layers run through the
            # SPMD 1F1B ring over the ``pipe`` axis, composed with fsdp/tp
            # via partial-manual shard_map.
            if cache is not None:
                raise NotImplementedError(
                    "KV-cache decode through the pipeline is not supported; "
                    "serve with a pipe=1 topology (the inference engines "
                    "shard with TP instead)")
            if use_rltd or pld_theta is not None:
                raise ValueError(
                    "pipeline parallelism is incompatible with random-LTD / "
                    "progressive layer dropping (they restructure the stack)")
            if kv_mask is not None or kv_positions is not None:
                raise NotImplementedError(
                    "kv_mask/kv_positions are not supported through the "
                    "pipelined trunk (they are decode-path arguments; train "
                    "packing uses segment_ids, which IS supported)")
            if not cfg.scan_layers:
                raise ValueError("pipeline parallelism requires "
                                 "scan_layers=True (stacked layer params)")
            from ..parallel.pipeline import spmd_pipeline

            lrngs = jax.random.split(rng, cfg.num_layers)
            stacked = {"w": params["layers"], "rng": lrngs}

            def pp_layer(lp, h, ex):
                pos, seg = ex
                h2, _, aux = self._layer(lp["w"], h, pos, seg, None,
                                         lp["rng"])
                return h2, aux

            x, aux_total = spmd_pipeline(
                pp_layer, stacked, x, wtopo,
                n_microbatches=cfg.pipe_microbatches,
                remat=cfg.remat, extras=(positions, segment_ids),
                with_aux=True)
        elif use_rltd:
            # Random layerwise token dropping (reference csrc/random_ltd/
            # token_sort/gather_scatter kernels + data_routing/basic_layer):
            # first and last layers see every token; the middle stack runs on
            # a random per-row subset of rltd_keep tokens (kept in causal
            # order), and dropped tokens skip those layers via the residual.
            lp = params["layers"]
            first = jax.tree_util.tree_map(lambda t: t[0], lp)
            mid = jax.tree_util.tree_map(lambda t: t[1:-1], lp)
            last = jax.tree_util.tree_map(lambda t: t[-1], lp)
            rngs = jax.random.split(rng, cfg.num_layers + 1)
            x, _, aux0 = self._layer(first, x, positions, segment_ids, None,
                                     rngs[0])

            def sample_idx(r):
                return jnp.sort(jax.random.permutation(r, s)[:rltd_keep])

            idx = jax.vmap(sample_idx)(jax.random.split(rngs[-1], b))
            x_sub = jnp.take_along_axis(x, idx[..., None], axis=1)
            pos_sub = jnp.take_along_axis(positions, idx, axis=1)
            seg_sub = (jnp.take_along_axis(segment_ids, idx, axis=1)
                       if segment_ids is not None else None)

            def mid_fn(xc, p, rng_l):
                xc, _, aux = self._layer(p, xc, pos_sub, seg_sub, None, rng_l)
                return xc, aux

            if cfg.remat:
                mid_fn = jax.checkpoint(mid_fn)

            def mid_body(xc, inp):
                p, rng_l = inp
                xc, aux = mid_fn(xc, p, rng_l)
                return xc, aux

            x_sub, auxes = jax.lax.scan(
                mid_body, x_sub, (mid, rngs[1:cfg.num_layers - 1]))
            x = x.at[jnp.arange(b)[:, None], idx].set(x_sub.astype(x.dtype))
            x, _, auxl = self._layer(last, x, positions, segment_ids, None,
                                     rngs[cfg.num_layers - 1])
            aux_total = aux0 + auxes.sum() + auxl
        elif cfg.scan_layers:
            dummy = jnp.zeros((cfg.num_layers, 0)) if cache is None else None
            ks = jax.random.split(rng, cfg.num_layers)
            # Progressive Layer Dropping (reference
            # runtime/progressive_layer_drop.py, arXiv:2010.13369): per-layer
            # keep prob p_l = 1 − (l+1)/L·(1−θ(t)); dropped layers skip via
            # lax.cond so they cost neither FLOPs nor activation memory.
            # Recorded decision: kept layers are NOT rescaled by 1/p_l
            # (stochastic-depth style), matching the paper and the
            # reference, which argue PreLN identity paths tolerate the
            # train(θ<1)/eval(all-layers) expectation gap; rescaling would
            # also change parity with reference-trained checkpoints.
            use_pld = (pld_theta is not None and train and cache is None)

            def body(x, inp):
                p, ck, cv, rng_l, li = inp
                if not use_pld:
                    x, nck, ncv, aux = layer_fn(x, p, ck, cv, rng_l, None)
                    return x, ((nck, ncv), aux)
                keep_p = 1.0 - (li + 1).astype(jnp.float32) / cfg.num_layers \
                    * (1.0 - pld_theta)
                keep = jax.random.bernoulli(jax.random.fold_in(rng_l, 17),
                                            keep_p)

                def run(_):
                    return layer_fn(x, p, ck, cv, rng_l, None)

                def skip(_):
                    return x, ck, cv, jnp.zeros((), jnp.float32)

                x, nck, ncv, aux = jax.lax.cond(keep, run, skip, None)
                return x, ((nck, ncv), aux)

            xs = (params["layers"],
                  cache.k if cache is not None else dummy,
                  cache.v if cache is not None else dummy,
                  ks, jnp.arange(cfg.num_layers))
            x, ((nk, nv), auxes) = jax.lax.scan(body, x, xs)
            aux_total = auxes.sum()
            if cache is not None:
                new_cache = KVCache(nk, nv, cache.write_pos + s)
        else:
            aux_total = jnp.zeros((), jnp.float32)
            nks, nvs = [], []
            use_pld = (pld_theta is not None and train and cache is None)
            for i, p in enumerate(params["layers"]):
                ck = cache.k[i] if cache is not None else None
                cv = cache.v[i] if cache is not None else None
                rng_l = jax.random.fold_in(rng, i)
                if use_pld:
                    keep_p = 1.0 - (i + 1) / cfg.num_layers \
                        * (1.0 - pld_theta)
                    keep = jax.random.bernoulli(
                        jax.random.fold_in(rng_l, 17), keep_p)
                    x, nck, ncv, aux = jax.lax.cond(
                        keep,
                        lambda _: layer_fn(x, p, ck, cv, rng_l, i),
                        lambda _: (x, ck, cv, jnp.zeros((), jnp.float32)),
                        None)
                else:
                    x, nck, ncv, aux = layer_fn(x, p, ck, cv, rng_l, i)
                aux_total = aux_total + aux
                if cache is not None:
                    nks.append(nck)
                    nvs.append(ncv)
            if cache is not None:
                new_cache = KVCache(jnp.stack(nks), jnp.stack(nvs),
                                    cache.write_pos + s)

        with region_scope("head"):  # final norm + LM head projection
            x = norm(x, params["final_norm"], cfg)
            if cfg.tie_embeddings:
                logits = jnp.einsum(
                    "bsd,vd->bsv", x,
                    params["embed"]["embedding"].astype(x.dtype))
            else:
                logits = jnp.einsum(
                    "bsd,dv->bsv", x,
                    params["lm_head"]["kernel"].astype(x.dtype))
                if cfg.lm_head_bias:
                    logits = logits + params["lm_head"]["bias"].astype(
                        logits.dtype)
        return logits.astype(jnp.float32), new_cache, aux_total

    def apply(self, params: Params, input_ids: jnp.ndarray, **kw) -> jnp.ndarray:
        return self._forward(params, input_ids, **kw)[0]

    # ------------------------------------------------------------------ loss
    def loss(self, params: Params, batch: Dict[str, jnp.ndarray],
             rng: Optional[jax.Array] = None, train: bool = True):
        """Next-token cross-entropy with optional ``labels``/``loss_mask``;
        the engine's ``loss_fn`` protocol. ``train=False`` disables
        train-only stochastic behavior (random-LTD token dropping)."""
        input_ids = batch["input_ids"]
        logits, _, aux = self._forward(
            params, input_ids,
            positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"), rng=rng,
            pld_theta=batch.get("pld_theta"), train=train)
        from ..monitor.mfu import region_scope

        with region_scope("loss"):  # softmax-xent MFU region
            if "labels" in batch:
                labels = batch["labels"]
                mask = batch.get("loss_mask",
                                 (labels >= 0).astype(jnp.float32))
                labels = jnp.maximum(labels, 0)
            else:
                labels = jnp.concatenate(
                    [input_ids[:, 1:], jnp.zeros_like(input_ids[:, :1])],
                    axis=1)
                mask = jnp.concatenate(
                    [jnp.ones_like(input_ids[:, 1:], jnp.float32),
                     jnp.zeros_like(input_ids[:, :1], jnp.float32)], axis=1)
                if "loss_mask" in batch:
                    mask = mask * batch["loss_mask"]
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            nll = (logz - gold) * mask
            denom = jnp.maximum(mask.sum(), 1.0)
            lm_loss = nll.sum() / denom
            total = lm_loss + self.config.aux_loss_coef * aux
        metrics = {"lm_loss": lm_loss}
        if self.config.any_moe:
            metrics["moe_aux_loss"] = aux
        return total, metrics

    # ------------------------------------------------------------------ decode
    def init_kv_cache(self, batch_size: int, max_len: int,
                      dtype=jnp.bfloat16) -> KVCache:
        cfg = self.config
        shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads,
                 cfg.head_dim)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                       jnp.zeros((), jnp.int32))

    def decode_step(self, params: Params, cache: KVCache,
                    tokens: jnp.ndarray,
                    positions: Optional[jnp.ndarray] = None,
                    kv_mask: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, KVCache]:
        """One incremental step over ``tokens`` [B, S] (S=1 for pure decode,
        larger for prefill/chunked-prefill). Returns (logits [B, S, V], cache).
        ``positions``/``kv_mask`` support ragged right-padded batches (see
        ``inference/engine.py``)."""
        logits, new_cache, _ = self._forward(params, tokens, positions=positions,
                                             cache=cache, kv_mask=kv_mask,
                                             kv_positions=kv_positions)
        return logits, new_cache

    # ------------------------------------------------------------------ sharding
    def sharding_rules(self, path, shape) -> Optional[Tuple]:
        """Megatron-style TP + explicit FSDP dims, composed by ``runtime/zero.py``
        (which strips ``fsdp`` below stage 3). Stacked layer leaves lead with the
        layer dim, which must never shard (scan iterates it)."""
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        s = "/".join(str(n) for n in names)
        stacked = self.config.scan_layers and any(
            n in names for n in ("layers", "dense_layers", "mamba_layers",
                                 "attn_layers", "lightning_layers",
                                 "ffn_layers", "hybrid_layers"))
        if stacked:
            # under pipeline parallelism the stacked layer dim shards over
            # ``pipe`` (each stage owns its contiguous layer block — the
            # PipelineModule partitioning); otherwise it must never shard
            # (scan iterates it). cfg.pipe_stages (engine-set) decides;
            # world topology is the direct-use fallback.
            if self.config.pipe_stages is not None:
                pipe = self.config.pipe_stages > 1
            else:
                from ..comm import topology as topo_mod

                t = topo_mod._WORLD_TOPOLOGY
                pipe = (t is not None and t.axis_sizes.get("pipe", 1) > 1)
            pre: Tuple = ("pipe",) if pipe else (None,)
        else:
            pre = ()

        if s.endswith("embed/embedding"):
            return ("model", "fsdp")
        if s.endswith("lm_head/kernel"):
            return ("fsdp", "model")
        if "attn/" in s or s.endswith(("wq", "wk", "wv", "wo")):
            if s.endswith(("wq", "wk", "wv")):
                return pre + ("fsdp", "model")
            if s.endswith("wo"):
                return pre + ("model", "fsdp")
        if s.endswith(("mlp/w_gate", "mlp/w_up", "mlp/fc1")):
            return pre + ("fsdp", "model")
        if s.endswith(("mlp/w_down", "mlp/fc2")):
            return pre + ("model", "fsdp")
        if s.endswith("pos_embed/embedding"):
            return ("model", "fsdp")  # looked up via vocab_parallel_embedding
        if s.endswith("moe/router"):
            return pre + (None, None)
        if s.endswith(("moe/w_gate", "moe/w_up")):
            return pre + ("expert", "fsdp", "model")
        if s.endswith("moe/w_down"):
            return pre + ("expert", "model", "fsdp")
        if s.endswith("scale"):
            return pre or None  # norm scales replicate (per pipe stage)
        return pre or None


def build_model(name_or_config, **overrides) -> CausalLM:
    """Model factory (registry analog of ``inference/v2/engine_factory.py:123``)."""
    if isinstance(name_or_config, ModelConfig):
        cfg = name_or_config
    else:
        cfg = get_config(name_or_config, **overrides)
    return CausalLM(cfg)
