"""Model configuration for the built-in transformer families.

The reference ships per-architecture *policies* that map external (HF) modules onto
its fused containers (``deepspeed/module_inject/containers/*.py``, 19 families) and a
v2 model zoo (``deepspeed/inference/v2/model_implementations/``: llama_v2, mistral,
mixtral, opt, falcon, phi). Here the framework owns the model definition outright —
one config dataclass covers the dense Llama/GPT family and the Mixtral-style MoE
family; per-family presets live in :data:`PRESETS`.
"""
import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, NamedTuple, Optional, Tuple


class MuP(NamedTuple):
    """muP's forward multipliers beyond the stream's three (``embed_scale``,
    ``residual_scale``, ``logit_scale``), under falcon_h1's published names
    less ``_multiplier``: twelve scalars, all 1.0 for every other family.
    ``attention_in`` / ``ssm_in`` scale the normed input of an ``H`` layer's
    halves, ``attention_out`` / ``ssm_out`` what each half writes; ``key``
    the keys of any attention; ``ssm`` the ``[z | x | B | C | dt]`` slices of
    a Mamba in-projection's output; ``mlp`` a gated MLP's gate (before its
    activation) and its down-projection's output. The parameter tree holds
    the UNSCALED matrices, as published."""
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp: Tuple[float, float] = (1.0, 1.0)


@dataclass
class ModelConfig:
    # Core dimensions
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None => MHA; < num_heads => GQA
    head_dim: Optional[int] = None      # None => hidden_size // num_heads
    max_seq_len: int = 4096

    # Architecture knobs. Together these cover the reference's per-arch policy
    # zoo (deepspeed/module_inject/containers/*.py — llama, gpt2, opt, bloom,
    # falcon, gptneox, gptj, phi, ...) as config axes on ONE model definition
    # instead of 19 module-surgery policies.
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    attn_impl: str = "auto"  # auto | xla | flash | ring | ulysses
    activation: str = "silu"   # silu | gelu | gelu_exact | relu | relu2
    use_bias: bool = False     # biases on attention/MLP projections
    qkv_bias: Optional[bool] = None  # override bias for q/k/v only (Qwen-style)
    attn_out_bias: Optional[bool] = None  # override bias for attn out proj (gptj)
    lm_head_bias: bool = False      # bias on the unembedding (gptj/phi)
    norm_type: str = "rmsnorm"      # rmsnorm | layernorm (learned bias)
    pos_embed: str = "rope"         # rope | learned | alibi | none
    alibi_scale: float = 1.0        # falcon-rw divides alibi by sqrt(head_dim)
    pos_embed_offset: int = 0       # OPT stores positions at offset 2
    rotary_pct: float = 1.0         # partial rotary (gpt-neox 0.25, phi 0.4)
    mlp_type: str = "glu"           # glu (gated, 3 mats) | mlp (fc1/fc2)
    parallel_block: bool = False    # attn+mlp both from norms of x (gptj/neox/falcon/phi)
    shared_block_norm: bool = False  # parallel block with ONE norm (gptj/falcon-7b/phi)
    embed_norm: bool = False        # layernorm right after embedding (bloom)
    sliding_window: Optional[int] = None  # Mistral-style local attention window
    # non-standard attention logit scale (None => 1/sqrt(head_dim); GPT-Neo
    # uses 1.0 — folded into q so every backend inherits it)
    attn_scale: Optional[float] = None
    # per-layer sliding windows (GPT-Neo alternating global/local pattern;
    # None entries = global). Heterogeneous layers, so requires
    # scan_layers=False (enforced in __post_init__).
    attn_windows: Optional[Tuple[Optional[int], ...]] = None
    # A PERIOD of attention kinds (cohere2_moe's ``layer_types``): layer l is
    # of kind attn_period[l % P], each ``(window or None, "rope" | "none")``,
    # a windowed kind and a full one in every period, and num_layers a
    # multiple of P. Unlike attn_windows the layers STAY STACKED: the
    # serving trunk scans periods and unrolls the P layers of one with
    # static kinds (inference/v2/model.py:_scan_layers), the windowed layers'
    # rows and the full layers' live in pools of their own and a windowed
    # row is given back once no query can see it (inference/v2/kv_cache.py).
    # sliding_window and pos_embed then say nothing. Serving only.
    attn_period: Optional[Tuple[Tuple[Optional[int], str], ...]] = None
    # norm_type "layernorm" without the learned bias (cohere): scale only
    norm_bias: bool = True
    # the logits are multiplied by it (cohere's logit_scale)
    logit_scale: float = 1.0

    # MoE (Mixtral-family; reference: deepspeed/moe/sharded_moe.py)
    num_experts: int = 0            # 0 => dense MLP
    num_experts_per_tok: int = 2    # top-k routing
    moe_layer_freq: int = 1         # every Nth layer is MoE
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_jitter: float = 0.0
    # divide the top-k router weights by their sum (mixtral); OLMoE's config
    # publishes norm_topk_prob=false and combines with the raw softmax mass
    norm_topk_prob: bool = True
    # RMSNorm with a learned scale over the WHOLE q and k projections
    # ([q_dim] / [kv_dim], eps rms_norm_eps), before the head split and rotary
    # (OLMoE, HF modeling_olmoe OlmoeAttention.q_norm/k_norm)
    qk_norm: bool = False
    # RMSNorm over each HEAD of q and of k ([head_dim], one learned scale
    # for q's heads and one for k's, eps rms_norm_eps), after the head split
    # and before rotary (the Keye / Qwen3 backbones)
    qk_head_norm: bool = False
    # A learned sparse-attention indexer (DeepSeek-V3.2's recipe, under the
    # sizes KeyeVL2's ``sa_config`` and GLM-5's config publish): index_heads
    # small heads of index_head_dim score every cached token against the
    # query, and attention reads the index_topk best of them only (all,
    # while the context is no longer). 0 = none. The serving pool then
    # caches one more row a token and layer, the indexer's one key, beside K
    # and V or beside a latent pool's one row (inference/v2/kv_cache.py).
    # index_rope_dim: the LEADING dims of an indexer head that rotate (0:
    # all of them, Keye's; GLM-5 rotates qk_rope_head_dim = 64 of its 128).
    # index_q_latent: the indexer's queries are a projection of latent
    # attention's normed QUERY LATENT c_q (q_lora_rank wide; the recipe's
    # own, GLM-5's) and not of the layer's normed row (Keye's, which has no
    # latent). Serving only (inference/v2/dsa.py).
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_rope_dim: int = 0
    index_q_latent: bool = False
    # Power retention (arXiv:2507.04239; brumby) in the place of softmax
    # attention, on the uniform block: retention_degree p > 0 turns it on
    # (2 is the one written). Every layer then keeps, per sequence and KV
    # head, a float32 state over the symmetric square of the key
    # (ops/retention.py: [head_dim, STATE_DIM(head_dim)] and a normaliser),
    # decayed by a learned gate a KV head (``g_proj``, with a bias), and NO
    # layer caches a key: num_kv_layers is 0, the serving pool has no rows
    # (inference/v2/kv_cache.py) and a sequence costs one state slot
    # whatever its context. Its logit scale is attn_scale (None:
    # 1/sqrt(head_dim)), inside the power. retention_chunk_size: rows of a
    # piece of the chunked form, as ssm_chunk_size is Mamba-2's.
    # retention_half_life: the range, in tokens, over which init_params
    # draws a head's gate bias (log-uniform). Serving only.
    retention_degree: int = 0
    retention_chunk_size: int = 256
    retention_half_life: Tuple[float, float] = (64.0, 8192.0)

    # The xing4_0 / DeepSeek-V3 family, under the names its config.json
    # publishes. Latent attention (MLA): kv_lora_rank > 0 turns it on; the
    # serving pool then caches ONE row a token and layer, the normed latent
    # and the rotated shared key (``latent_kv_dim``), and no V.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # {"type": "yarn", "factor", "original_max_position_embeddings",
    # "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} or None
    rope_scaling: Optional[Dict[str, Any]] = None
    # layers < first_k_dense_replace keep a dense MLP of intermediate_size;
    # the rest route over experts of moe_intermediate_size (None: experts
    # are intermediate_size wide, as mixtral's and OLMoE's)
    first_k_dense_replace: int = 0
    moe_intermediate_size: Optional[int] = None
    n_shared_experts: int = 0       # always-on experts beside the routed
    # how the shared experts join the routed sum: "sum" adds each, "average"
    # adds their MEAN (cohere2_moe's shared_expert_combination_strategy)
    shared_expert_combine: str = "sum"
    scoring_func: str = "softmax"   # softmax | sigmoid router scores
    # "noaux_tc": a learned bias joins the scores for the top-k CHOICE only;
    # "group_limited_greedy" (DeepSeek-V2): the experts lie in n_group equal
    # groups, a token keeps the topk_group groups whose best score is
    # highest and takes its top-k among their experts alone
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # Expert parallelism's share of a layer: of the router's num_experts
    # this program HOLDS num_experts_held (None: all), the contiguous ids
    # from first_expert_held. It routes over all of them and computes its
    # own experts' part of the result (parallel/moe.moe_mlp_nodrop).
    # first_expert_held is the deployment's to say and 0 in every preset and
    # cell: only tests/unit/test_expert_share.py and tests/benchmark/
    # test_deepseek_v2.py set it, to add the four shares up to the uncut
    # layer; it stays a field until an exchange across chips names a rank
    num_experts_held: Optional[int] = None
    first_expert_held: int = 0
    # manifold-constrained hyper-connections: hc_mult residual streams,
    # mixed by a Sinkhorn-normalised matrix in every sublayer (1 = the
    # plain x + f(norm(x)) stream)
    # A hybrid stack (nemotron_h, solar_open2): one character a layer, each
    # layer ONE mixer behind one norm: ``M`` a Mamba-2 mixer, ``K`` a gated
    # delta-rule mixer (below), ``E`` sparse experts (nemotron_h's two-matrix,
    # ``mlp_type="mlp"``; solar_open2's gated), ``*`` attention, ``L`` a
    # lightning linear-attention mixer and ``F`` a dense feed-forward part
    # alone (minicpm_sala; both below). None: the uniform attention + MLP
    # block. ``H`` (falcon_h1) is the one letter with TWO mixers: attention
    # heads and a Mamba-2 mixer side by side behind ONE norm, their outputs
    # summed into one residual add; it stands beside ``E`` and ``F`` only.
    # A stack of parameters a kind
    # (``mamba_layers``, ``kda_layers``, ``layers``, ``attn_layers``,
    # ``hybrid_layers``), a KV pool with a row for the ``*`` and ``H`` layers
    # only and a recurrent state per sequence slot for the ``M``, ``K``,
    # ``L`` or ``H`` layers (inference/v2/kv_cache.py): an ``H`` layer has
    # both, at the same index. A published layer that is a mixer AND an
    # expert or feed-forward block (solar_open2, falcon_h1) is two
    # characters here, so num_layers counts characters. Serving only.
    layer_pattern: Optional[str] = None
    # The gated delta rule with a decay per key channel (Kimi delta
    # attention; ops/kda.py), the ``K`` layers of a layer_pattern:
    # kda_num_heads heads (0: none) of kda_head_dim for key and value alike,
    # behind ONE depthwise convolution of kda_conv_kernel over q | k | v;
    # the decay and the output gate come through pairs of projections of
    # rank kda_gate_rank (solar_open2: kda_use_full_proj false, the rank a
    # head's width); the write's strength is kda_beta_scale x sigmoid, 2
    # where the transition may have negative eigenvalues
    # (kda_allow_neg_eigval). Every ``K`` layer keeps, per sequence, a
    # float32 state [heads, head_dim, head_dim] and the convolution's tail.
    # kda_chunk_size: rows of a piece of the chunked form, as
    # ssm_chunk_size is Mamba-2's. A and dt_bias are drawn as Mamba-2's
    # (time_step_min .. time_step_max below).
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 0
    kda_beta_scale: float = 1.0
    kda_chunk_size: int = 64
    # softmax attention's output is multiplied by sigmoid(x Wg), elementwise
    # over all q_dim values, from the row the queries read (solar_open2's
    # use_gqa_gate; the ``*`` layers of a layer_pattern)
    attn_out_gate: bool = False
    # Lightning linear attention (arXiv:2401.04658; minicpm_sala's
    # "lightning-attn"), the ``L`` layers of a layer_pattern: lightning_heads
    # heads (0: none) of lightning_head_dim for query, key and value alike,
    # S_t = lambda_h S_{t-1} + k_t v_t^T with the FIXED decay lambda_h =
    # exp(-2^(-8 (h + 1) / heads)) a head, o_t = q_t^T S_t / sqrt(dim): no
    # softmax, no normaliser, no convolution; q and k normed a head and
    # rotated over the whole head at rope_theta, the heads' outputs normed
    # over all of them together and gated. Every ``L`` layer keeps, per
    # sequence, a float32 state [heads, dim, dim] (ops/ssm.py: Mamba-2's
    # recurrence with a group a head). lightning_chunk_size: rows of a
    # piece of the chunked form, as ssm_chunk_size is Mamba-2's.
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_chunk_size: int = 128
    # Block-sparse attention chosen from pooled keys (InfLLM-v2; MiniCPM4's
    # sparse_config), on the ``*`` layers of a layer_pattern:
    # sparse_block_topk blocks of sparse_block_size tokens a query and KV
    # GROUP reads (0: every block), of which the first sparse_block_init,
    # the sparse_block_window ending with the query's own and the rest by
    # score: the softmax of the group's queries over the MEAN of each
    # window of sparse_block_kernel keys (every sparse_block_stride), summed
    # over the group's heads and max-pooled to blocks. A row whose context
    # is under sparse_block_dense_len reads every block. The serving pool's
    # block_size must equal sparse_block_size: a selected block is a page.
    # (ops/sparse_block.py, inference/v2/bsa.py.)
    sparse_block_topk: int = 0
    sparse_block_size: int = 64
    sparse_block_kernel: int = 32
    sparse_block_stride: int = 16
    sparse_block_init: int = 1
    sparse_block_window: int = 32
    sparse_block_dense_len: int = 8192
    # muP's scalings of the stream (minicpm's scale_emb and scale_depth /
    # sqrt(published layers)): the embedding is multiplied by embed_scale,
    # every sublayer of a layer_pattern is x + residual_scale f(norm(x));
    # 1.0: neither (logit_scale is the third)
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    # ... and the twelve further multipliers of a family that scales inside
    # the layer too (falcon_h1; a mapping of MuP's names is taken as one)
    mup: MuP = MuP()
    # Mamba-2 sizes, under the names nemotron_h publishes: d_inner is
    # mamba_num_heads x mamba_head_dim (not an expansion of hidden_size);
    # B and C come in ssm_n_groups groups of ssm_state_size
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_n_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk_size: int = 128       # rows of a piece of the chunked scan
    time_step_min: float = 0.001    # the range dt is drawn over at init
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the shared expert's width where it is not n_shared_experts x one
    # routed expert's (nemotron_h: moe_shared_expert_intermediate_size)
    shared_expert_intermediate_size: Optional[int] = None
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # A looped stack (ouro), under the names its config.json publishes: the
    # SAME num_layers layers run total_ut_steps times a token. The final
    # norm closes every pass and its output starts the next; a pass caches
    # its own keys and values (the KV pool's row ``pass x num_layers +
    # layer``) and an exit gate (``params["exit_gate"]``) reads each pass's
    # output: the logits are those of the first pass at which the running
    # sum of the exit distribution reaches early_exit_threshold (at 1.0 the
    # last). Every pass is computed whatever the threshold. Serving only.
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # each sublayer's OUTPUT is normed too (``attn_post_norm``,
    # ``mlp_post_norm``) before it joins the stream: x + norm(f(norm(x)))
    sandwich_norm: bool = False

    # Training-time behavior
    remat: bool = False             # jax.checkpoint each layer (activation ckpt)
    # a jax.checkpoint_policies name, a rung of models/remat.py, or "auto":
    # the richest rung that fits remat_free_bytes (one device's memory less
    # the engine's resident state; None: no limit known, nothing is saved)
    remat_policy: Optional[str] = None
    remat_free_bytes: Optional[int] = None
    scan_layers: bool = True        # lax.scan over stacked layer params
    # pipeline microbatches per forward when the topology has pipe>1
    # (None => number of stages); config key pipeline.micro_batches
    pipe_microbatches: Optional[int] = None
    # pipe-stage count the trunk is built for. The engine sets this from its
    # topology at init so the pipelined trunk is an EXPLICIT config property
    # (visible to jit retracing), not a hidden global read; None falls back
    # to the world topology's pipe axis for direct model use.
    pipe_stages: Optional[int] = None
    dropout: float = 0.0
    dtype: str = "bfloat16"         # compute dtype hint (engine may override)
    # Random layerwise token dropping (reference csrc/random_ltd/ +
    # data_pipeline/data_routing): middle layers process only
    # random_ltd_current randomly kept tokens (engine schedules the value)
    random_ltd: bool = False
    random_ltd_current: Optional[int] = None

    # Initializer
    initializer_range: float = 0.02
    # a routed expert's w_down as init_params SEEDS it beside shared experts,
    # as a share of their rule (None: 1 / num_experts; a checkpoint
    # overwrites it). It sets how much of a logit the routed experts carry
    # when a seeded model is held against its reference: deepseek-v2 below
    routed_write_share: Optional[float] = None

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.qkv_bias is None:
            self.qkv_bias = self.use_bias
        if self.attn_out_bias is None:
            self.attn_out_bias = self.use_bias
        if self.norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(f"unknown norm_type {self.norm_type!r}")
        if self.pos_embed not in ("rope", "learned", "alibi", "none"):
            raise ValueError(f"unknown pos_embed {self.pos_embed!r}")
        if self.mlp_type not in ("glu", "mlp"):
            raise ValueError(f"unknown mlp_type {self.mlp_type!r}")
        if self.shared_block_norm and not self.parallel_block:
            raise ValueError("shared_block_norm requires parallel_block")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring_func {self.scoring_func!r}")
        if self.topk_method not in ("greedy", "noaux_tc",
                                    "group_limited_greedy"):
            raise ValueError(f"unknown topk_method {self.topk_method!r}")
        if self.topk_method == "group_limited_greedy" and (
                self.num_experts % self.n_group
                or not 0 < self.topk_group <= self.n_group
                or self.topk_group * (self.num_experts // self.n_group)
                < self.num_experts_per_tok):
            raise ValueError(
                f"group_limited_greedy: {self.num_experts} experts in "
                f"{self.n_group} groups, the best {self.topk_group} kept, "
                f"cannot give {self.num_experts_per_tok} a token")
        if not (0 <= self.first_expert_held and self.first_expert_held
                + self.experts_held <= self.num_experts):
            raise ValueError(
                f"experts {self.first_expert_held} to "
                f"{self.first_expert_held + self.experts_held} held of "
                f"{self.num_experts}")
        if self.shared_expert_combine not in ("sum", "average"):
            raise ValueError(f"unknown shared_expert_combine "
                             f"{self.shared_expert_combine!r}")
        if self.attn_period is not None:
            self._check_period()
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm (over the whole projection) and "
                             "qk_head_norm (a head at a time): one of them")
        if self.index_topk and (
                not (self.index_heads and self.index_head_dim)
                or self.sliding_window
                or self.pos_embed != "rope" or self.layer_pattern is not None
                or self.total_ut_steps > 1 or self.hc_mult > 1
                or self.attn_windows is not None
                or self.attn_period is not None):
            raise ValueError(
                "index_topk: the sparse-attention indexer needs index_heads "
                "and index_head_dim, rotary positions and one uniform stack "
                "of K-and-V or of latent attention (no window, "
                "layer_pattern, looped stack, hyper-connection streams or "
                "period of attention kinds)")
        if self.index_rope_dim % 2 or not (
                0 <= self.index_rope_dim <= self.index_head_dim):
            raise ValueError(
                f"index_rope_dim {self.index_rope_dim}: an even count of "
                f"the indexer head's {self.index_head_dim} dims (0: all)")
        if self.index_q_latent and not (self.index_topk
                                        and self.kv_lora_rank):
            raise ValueError(
                "index_q_latent: the indexer's queries come of latent "
                "attention's query latent, so index_topk and kv_lora_rank")
        if self.retention_degree and (
                self.retention_degree != 2 or self.kv_lora_rank
                or self.index_topk or self.sliding_window
                or self.layer_pattern is not None or self.total_ut_steps > 1
                or self.hc_mult > 1 or self.attn_windows is not None
                or self.attn_period is not None
                or self.pos_embed == "alibi" or not self.scan_layers
                or self.head_dim % 2):
            raise ValueError(
                "retention_degree: power retention is written for degree 2 "
                "on one uniform stack (scan_layers) of an even head_dim: no "
                "latent attention, indexer, window, alibi, layer_pattern, "
                "looped stack, hyper-connection streams or period of "
                "attention kinds")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"unknown rope_scaling {self.rope_scaling!r}")
        if self.first_k_dense_replace and (
                not self.any_moe or self.moe_layer_freq != 1
                or self.first_k_dense_replace >= self.num_layers
                or self.attn_windows is not None):
            raise ValueError(
                "first_k_dense_replace needs experts in every later layer, "
                "stacked (scan_layers)")
        if not isinstance(self.mup, MuP):    # a mapping or its values
            m = MuP(**self.mup) if isinstance(self.mup, dict) \
                else MuP(*self.mup)
            self.mup = m._replace(ssm=tuple(m.ssm), mlp=tuple(m.mlp))
        if self.mup._replace(mlp=(1.0, 1.0)) != MuP() \
                and "H" not in (self.layer_pattern or ""):
            raise ValueError(
                "mup: the attention_*, key and ssm_* multipliers are an 'H' "
                "layer's (layer_pattern); only mup.mlp is read elsewhere")
        if self.layer_pattern is not None:
            self._check_pattern()
        elif (self.attn_out_gate or self.kda_num_heads
              or self.lightning_heads or self.sparse_block_topk
              or self.residual_scale != 1.0):
            raise ValueError(
                "attn_out_gate, kda_num_heads, lightning_heads, "
                "sparse_block_topk and residual_scale belong to a "
                "layer_pattern ('*', 'K' and 'L' layers): the uniform block "
                "has none of them")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps} < 1")
        if self.total_ut_steps > 1 or self.sandwich_norm:
            self._check_loop()
        if self.attn_windows is not None:
            self.attn_windows = tuple(self.attn_windows)
            if len(self.attn_windows) != self.num_layers:
                raise ValueError(
                    f"attn_windows has {len(self.attn_windows)} entries for "
                    f"{self.num_layers} layers")
            if self.scan_layers:
                # per-layer windows make layers heterogeneous — the stacked
                # lax.scan trunk requires identical layer programs
                self.scan_layers = False

    def _check_pattern(self):
        pat = self.layer_pattern
        if set(pat) - set("MKLEFH*") or len(pat) != self.num_layers:
            raise ValueError(
                f"layer_pattern {pat!r}: {self.num_layers} characters of "
                f"'M' (Mamba-2), 'K' (gated delta rule), 'L' (lightning "
                f"attention), 'E' (experts), 'F' (dense feed-forward), "
                f"'H' (attention and Mamba-2 side by side) and '*' "
                f"(attention) wanted")
        if "H" in pat and (set(pat) & set("MKL*") or self.attn_out_gate
                           or self.sparse_block_topk or self.index_topk):
            raise ValueError(
                "layer_pattern: an 'H' layer brings its own attention and "
                "its own Mamba-2 mixer, a KV row and a state slot at ONE "
                "index: no 'M', 'K', 'L' or '*' layer beside it, and plain "
                "attention (no output gate, selected blocks or indexer)")
        if "L" in pat and ("M" in pat or "K" in pat or not (
                self.lightning_heads and self.lightning_head_dim
                and self.lightning_head_dim % 2 == 0)):
            raise ValueError(
                "layer_pattern: 'L' layers need lightning_heads and an even "
                "lightning_head_dim, and no 'M' or 'K' layer beside them "
                "(ONE kind of recurrent state a model)")
        if self.sparse_block_topk:
            self._check_sparse_block()
        if "K" in pat and ("M" in pat or not (
                self.kda_num_heads and self.kda_head_dim
                and self.kda_gate_rank and self.kda_conv_kernel > 1)):
            raise ValueError(
                "layer_pattern: 'K' layers need kda_num_heads, kda_head_dim, "
                "kda_gate_rank and a kda_conv_kernel of 2 or more, and no "
                "'M' layer beside them (ONE kind of recurrent state a model)")
        if ("E" in pat) != self.any_moe:
            raise ValueError("layer_pattern: 'E' layers need num_experts, "
                             "and num_experts needs an 'E' layer")
        if self.mamba_layers and not (
                self.mamba_num_heads and self.mamba_head_dim
                and self.ssm_state_size
                and self.mamba_num_heads % self.ssm_n_groups == 0):
            raise ValueError("layer_pattern: 'M' and 'H' layers need "
                             "mamba_num_heads (a multiple of ssm_n_groups), "
                             "mamba_head_dim and ssm_state_size")
        if (self.kv_lora_rank or self.hc_mult > 1 or self.parallel_block
                or self.first_k_dense_replace or self.moe_layer_freq != 1
                or self.attn_windows is not None
                or self.attn_period is not None or not self.scan_layers):
            raise ValueError(
                "layer_pattern walks three plain stacks (scan_layers): no "
                "latent attention, hyper-connection streams, parallel "
                "block, leading dense layers, per-layer windows or period "
                "of attention kinds")
        if self.attn_out_gate and (self.index_topk or self.qkv_bias
                                   or self.attn_out_bias):
            raise ValueError(
                "attn_out_gate: the output gate is written for the plain "
                "K-and-V attention of a layer_pattern's '*' layers: no "
                "indexer and no attention bias")

    def _check_sparse_block(self):
        """The block-sparse attention's sizes, and what it is not written
        beside."""
        bs, kern, stride = (self.sparse_block_size, self.sparse_block_kernel,
                            self.sparse_block_stride)
        forced = self.sparse_block_init + self.sparse_block_window
        if (min(bs, kern, stride) < 1 or bs % stride or kern % stride
                or kern > bs or self.sparse_block_init < 0
                or self.sparse_block_window < 1
                or self.sparse_block_topk < forced
                or self.sparse_block_dense_len < forced * bs):
            raise ValueError(
                "sparse_block_*: the stride divides the kernel and the "
                "block, the kernel is at most a block, the blocks read "
                "(sparse_block_topk) hold the first sparse_block_init and "
                "the window's sparse_block_window, and a context of "
                "sparse_block_dense_len holds those apart")
        if ("*" not in self.layer_pattern or self.index_topk
                or self.pos_embed == "alibi" or self.qkv_bias):
            raise ValueError(
                "sparse_block_topk: block-sparse attention is written for "
                "the plain K-and-V attention of a layer_pattern's '*' "
                "layers: no indexer, alibi or attention bias")

    def _check_period(self):
        """``attn_period`` as tuples, and what a period of attention kinds
        is not walked with, each by name."""
        try:
            period = tuple((None if w is None else int(w), str(pos))
                           for w, pos in self.attn_period)
        except (TypeError, ValueError):
            raise ValueError(
                f"attn_period {self.attn_period!r}: (window or None, "
                f"'rope' | 'none') a layer of the period") from None
        self.attn_period = period
        windows = [w for w, _ in period]
        if (any(pos not in ("rope", "none") for _, pos in period)
                or any(w is not None and w < 1 for w in windows)
                or None not in windows or all(w is None for w in windows)
                or len({w for w in windows if w is not None}) != 1
                or self.num_layers % len(period)):
            raise ValueError(
                f"attn_period {period!r}: kinds (window or None, 'rope' | "
                f"'none'), a windowed kind (ONE window) and a full one in "
                f"every period, and num_layers {self.num_layers} a multiple "
                f"of its length (a stack of one kind is sliding_window and "
                f"pos_embed)")
        wrong = [name for name, on in (
            ("latent attention (kv_lora_rank)", bool(self.kv_lora_rank)),
            ("a window for all layers (sliding_window)",
             self.sliding_window is not None),
            ("per-layer windows (attn_windows)",
             self.attn_windows is not None),
            ("alibi or learned positions (pos_embed)",
             self.pos_embed not in ("rope", "none")),
            ("leading dense layers (first_k_dense_replace)",
             bool(self.first_k_dense_replace)),
            ("hyper-connection streams (hc_mult)", self.hc_mult > 1),
            ("unstacked layers (scan_layers false)", not self.scan_layers),
            ("pipeline stages (pipe_stages)",
             self.pipe_stages not in (None, 1))) if on]
        if wrong:
            raise ValueError(
                "attn_period walks ONE stack of K-and-V attention layers a "
                "period at a time; not written for: " + ", ".join(wrong))

    @property
    def attn_kinds(self) -> Tuple[Tuple[Optional[int], str], ...]:
        """The period of attention kinds the serving trunk unrolls, ``(window
        or None, positions)`` a layer: ``attn_period``, or the ONE kind of
        every other stack."""
        return self.attn_period or ((self.sliding_window, self.pos_embed),)

    @property
    def window_layers(self) -> int:
        """Layers whose cached rows live in the WINDOW pool (0: the model
        has one pool; a stack that is windowed throughout keeps every row,
        as it always did)."""
        if self.attn_period is None:
            return 0
        per = sum(w is not None for w, _ in self.attn_period)
        return self.num_layers // len(self.attn_period) * per

    @property
    def period_window(self) -> Optional[int]:
        """The window of ``attn_period``'s windowed kind (None: no period)."""
        return next((w for w, _ in self.attn_period or () if w is not None),
                    None)

    def _check_loop(self):
        """What a looped stack (or its post-sublayer norms) is not walked
        with, each by name."""
        wrong = [name for name, on in (
            ("layer_pattern", self.layer_pattern is not None),
            ("experts (num_experts)", self.any_moe),
            ("leading dense layers (first_k_dense_replace)",
             bool(self.first_k_dense_replace)),
            ("hyper-connection streams (hc_mult)", self.hc_mult > 1),
            ("a parallel block", self.parallel_block),
            ("per-layer windows (attn_windows)",
             self.attn_windows is not None),
            ("a period of attention kinds (attn_period)",
             self.attn_period is not None),
            ("unstacked layers (scan_layers false)", not self.scan_layers),
            ("pipeline stages (pipe_stages)",
             self.pipe_stages not in (None, 1))) if on]
        if wrong:
            raise ValueError(
                "total_ut_steps > 1 / sandwich_norm walk ONE uniform stack "
                "of sequential blocks; not written for: " + ", ".join(wrong))

    def pattern_count(self, kind: str) -> int:
        """Layers of ``kind`` ('M', 'K', 'L', 'E', 'F', 'H', '*') in
        ``layer_pattern``."""
        return (self.layer_pattern or "").count(kind)

    @property
    def mamba_layers(self) -> int:
        """Layers with a Mamba-2 mixer: alone (``M``) or beside attention
        heads (``H``)."""
        return self.pattern_count("M") + self.pattern_count("H")

    @property
    def mup_in_proj(self):
        """[d_inner + conv_dim + heads] float32: what a Mamba in-projection's
        output is multiplied by under ``mup``: ``ssm_in`` (folded out of the
        input: the product is linear) times ``ssm``'s multiplier of the
        column's slice, ``[z | x | B | C | dt]``. None: by nothing."""
        import numpy as np

        if (self.mup.ssm_in, self.mup.ssm) == (1.0, (1.0,) * 5):
            return None
        gn = self.ssm_n_groups * self.ssm_state_size
        widths = (self.ssm_d_inner, self.ssm_d_inner, gn, gn,
                  self.mamba_num_heads)
        return self.mup.ssm_in * np.repeat(
            np.asarray(self.mup.ssm, np.float32), widths)

    @property
    def num_kv_layers(self) -> int:
        """Rows of the KV pool's leading axis: one for every (pass, layer)
        pair that caches keys and values. A looped stack's pass ``u`` has
        rows ``u x L .. u x L + L - 1``: a pass attends to its own. A
        power-retention stack caches no key: 0. Under ``attn_period`` the
        FULL layers' rows: the windowed layers' (``window_layers``) lie in
        a pool of their own. Of a ``layer_pattern`` the ``*`` and the ``H``
        layers' (a model has one of the two)."""
        if self.retention_degree:
            return 0
        layers = self.num_layers - self.window_layers \
            if self.layer_pattern is None \
            else self.pattern_count("*") + self.pattern_count("H")
        return self.total_ut_steps * layers

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state per sequence (0: none): a
        ``layer_pattern``'s Mamba-2 (alone or beside attention heads),
        delta-rule or lightning layers, or every layer of a power-retention
        stack."""
        return self.num_layers if self.retention_degree \
            else sum(map(self.pattern_count, "MKLH"))

    @property
    def state_chunk_size(self) -> int:
        """Rows of a piece of the state layers' chunked form."""
        if self.pattern_count("K"):
            return self.kda_chunk_size
        if self.pattern_count("L"):
            return self.lightning_chunk_size
        return self.retention_chunk_size if self.retention_degree \
            else self.ssm_chunk_size

    @property
    def kda_dim(self) -> int:
        """Width of each of a delta-rule layer's q, k and v: heads x
        head_dim (the convolution runs over three of them)."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def lightning_dim(self) -> int:
        """Width of each of a lightning layer's q, k, v and gate."""
        return self.lightning_heads * self.lightning_head_dim

    @property
    def ssm_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the Mamba mixer's convolution: x | B | C."""
        return self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_state_size

    @property
    def rotary_dim(self) -> int:
        """Rotated prefix of head_dim (the rest passes through un-rotated)."""
        rd = int(self.head_dim * self.rotary_pct)
        return rd - rd % 2

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def latent_kv_dim(self) -> int:
        """Width of the ONE row latent attention caches a token and layer:
        the normed latent and the rotated key every head shares (0: K and V
        per head)."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this program has: all the router's,
        or the ``num_experts_held`` of an expert-parallel share."""
        return self.num_experts if self.num_experts_held is None \
            else self.num_experts_held

    @property
    def shared_expert_width(self) -> int:
        """Width of the ONE MLP the shared experts are (0: none)."""
        if self.shared_expert_intermediate_size is not None:
            return self.shared_expert_intermediate_size
        return self.n_shared_experts * (self.moe_intermediate_size
                                        or self.intermediate_size)

    @property
    def expert_width_stored(self) -> int:
        """A routed expert's width as its matrices are STORED: one wider
        than the TPU's 128 lanes is rounded up to a multiple of them, the
        extra columns of w_up (w_gate) and rows of w_down zero: the same
        function (act(0) = 0 for every activation here), in a shape whose
        minor dimension fills the lanes. At 1856 wide the compiler otherwise
        stores ``[E, d, 1856]`` transposed and copies the whole stack (3.3
        GB at nemotron-3-nano's cut) back for the grouped kernel, every
        forward (PERF.md section 6, PR 37). Every other preset's width is
        a multiple already; one under 128 (a test's) stays as drawn."""
        f = self.moe_intermediate_size or self.intermediate_size
        return f if f < 128 else -(-f // 128) * 128

    @property
    def held_experts(self) -> slice:
        """The experts held, as the columns of the router's width."""
        return slice(self.first_expert_held,
                     self.first_expert_held + self.experts_held)

    @property
    def num_moe_layers(self) -> int:
        if self.layer_pattern is not None:
            return self.pattern_count("E")
        return self.num_layers - self.first_k_dense_replace \
            if self.any_moe else 0

    @property
    def softmax_scale(self) -> Optional[float]:
        """Latent attention's logit scale: 1/sqrt(qk_nope + qk_rope) times
        YaRN's mscale(factor, mscale_all_dim) squared (DeepSeek-V2's
        ``softmax_scale * mscale * mscale``)."""
        if not self.kv_lora_rank:
            return None
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim") and rs["factor"] > 1:
            m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
            scale *= m * m
        return scale

    def is_moe_layer(self, layer_idx: int) -> bool:
        return self.num_experts > 0 and (
            layer_idx % self.moe_layer_freq == 0) and (
            layer_idx >= self.first_k_dense_replace)

    @property
    def any_moe(self) -> bool:
        return self.num_experts > 0

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + layers)."""
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.kv_lora_rank:
            h, qk = self.num_heads, self.qk_nope_head_dim
            attn = (d * self.q_lora_rank
                    + self.q_lora_rank * h * (qk + self.qk_rope_head_dim)
                    + d * self.latent_kv_dim
                    + self.kv_lora_rank * h * (qk + self.v_head_dim)
                    + h * self.v_head_dim * d)
        mats = 3 if self.mlp_type == "glu" else 2
        dense = mats * d * f
        fe = self.moe_intermediate_size or f
        moe = (mats * d * (fe * self.experts_held + self.shared_expert_width)
               + d * self.num_experts)
        if self.layer_pattern is not None:
            di, h = self.ssm_d_inner, self.mamba_num_heads
            mamba = (d * (di + self.ssm_conv_dim + h) + di * d
                     + self.ssm_conv_dim * (self.ssm_conv_kernel + 1)
                     + 3 * h + di + d)
            dk, r = self.kda_dim, self.kda_gate_rank
            kda = (4 * d * dk + 2 * (d * r + r * dk) + d * self.kda_num_heads
                   + 3 * dk * self.kda_conv_kernel + self.kda_num_heads
                   + dk + self.kda_head_dim + d)
            if self.attn_out_gate:
                attn += d * self.q_dim
            if self.qk_head_norm:
                attn += 2 * self.head_dim
            dl = self.lightning_dim
            light = 5 * d * dl + 2 * self.lightning_head_dim + dl + d
            return (mamba * self.pattern_count("M")
                    # (one norm feeds both halves: mamba's count has it)
                    + (mamba + attn) * self.pattern_count("H")
                    + kda * self.pattern_count("K")
                    + light * self.pattern_count("L")
                    + (dense + d) * self.pattern_count("F")
                    + (moe + d) * self.pattern_count("E")
                    + (attn + d) * self.pattern_count("*")
                    + v * d * (1 if self.tie_embeddings else 2) + d)
        if self.qk_norm:
            attn += self.q_dim + self.kv_dim
        if self.qk_head_norm:
            attn += 2 * self.head_dim
        if self.retention_degree:    # g_proj and its bias
            attn += (d + 1) * self.num_kv_heads
        if self.index_topk:    # w_qi, w_ki + its LayerNorm, w_w
            hi, di = self.index_heads, self.index_head_dim
            attn += (self.q_lora_rank if self.index_q_latent else d) \
                * hi * di + d * (di + hi) + 2 * di
        n_moe = self.num_moe_layers
        hc = 2 * (self.hc_mult * d * (2 + self.hc_mult) * self.hc_mult
                  + self.hc_mult * d) if self.hc_mult > 1 else 0
        # weights a looped stack shares between passes are counted once
        norms = 4 * d if self.sandwich_norm else 2 * d
        total = ((attn + norms + hc) * self.num_layers + moe * n_moe
                 + dense * (self.num_layers - n_moe) + v * d + d)
        if self.total_ut_steps > 1:
            total += d + 1    # the exit gate
        if not self.tie_embeddings:
            total += d * v
        return total


def _p(**kw) -> ModelConfig:
    return ModelConfig(**kw)


PRESETS = {
    # Test-scale configs (CI / CPU-mesh friendly)
    "tiny": _p(vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
               num_heads=4, num_kv_heads=2, max_seq_len=256),
    "tiny-moe": _p(vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
                   num_heads=4, num_kv_heads=2, max_seq_len=256, num_experts=4,
                   num_experts_per_tok=2),
    "small": _p(vocab_size=8192, hidden_size=512, intermediate_size=1408,
                num_layers=8, num_heads=8, num_kv_heads=8, max_seq_len=2048),
    # GPT-2/BERT-era scale (BASELINE config #1 family)
    # NOTE: 50257 matches real HF GPT-2 checkpoints for ingestion parity; pad
    # vocab (e.g. 50304) via overrides when running vocab-TP at degree > 1
    "gpt2-small": _p(vocab_size=50257, hidden_size=768, intermediate_size=3072,
                     num_layers=12, num_heads=12, max_seq_len=1024,
                     tie_embeddings=True, norm_type="layernorm",
                     pos_embed="learned", mlp_type="mlp", activation="gelu",
                     use_bias=True),
    "gpt2-xl": _p(vocab_size=50257, hidden_size=1600, intermediate_size=6400,
                  num_layers=48, num_heads=25, max_seq_len=1024,
                  tie_embeddings=True, norm_type="layernorm",
                  pos_embed="learned", mlp_type="mlp", activation="gelu",
                  use_bias=True),
    "bert-large-like": _p(vocab_size=30592, hidden_size=1024, intermediate_size=4096,
                          num_layers=24, num_heads=16, max_seq_len=512,
                          norm_type="layernorm", pos_embed="learned",
                          mlp_type="mlp", activation="gelu_exact",
                          use_bias=True),
    # The wider module_inject policy zoo (containers/{opt,bloom,gptneox,gptj}.py
    # + v2 model_implementations/{opt,falcon,phi}) as config presets:
    "opt-1.3b": _p(vocab_size=50272, hidden_size=2048, intermediate_size=8192,
                   num_layers=24, num_heads=32, max_seq_len=2048,
                   tie_embeddings=True, norm_type="layernorm",
                   pos_embed="learned", pos_embed_offset=2, mlp_type="mlp",
                   activation="relu", use_bias=True),
    "bloom-7b1": _p(vocab_size=250880, hidden_size=4096, intermediate_size=16384,
                    num_layers=30, num_heads=32, max_seq_len=2048,
                    tie_embeddings=True, norm_type="layernorm",
                    pos_embed="alibi", mlp_type="mlp", activation="gelu",
                    use_bias=True, embed_norm=True),
    "falcon-7b": _p(vocab_size=65024, hidden_size=4544, intermediate_size=18176,
                    num_layers=32, num_heads=71, num_kv_heads=1,
                    max_seq_len=2048, tie_embeddings=True,
                    norm_type="layernorm", mlp_type="mlp",
                    activation="gelu_exact",  # HF falcon uses erf gelu
                    parallel_block=True, shared_block_norm=True),
    "phi-2": _p(vocab_size=51200, hidden_size=2560, intermediate_size=10240,
                num_layers=32, num_heads=32, max_seq_len=2048,
                norm_type="layernorm", mlp_type="mlp", activation="gelu",
                use_bias=True, rotary_pct=0.4, parallel_block=True,
                shared_block_norm=True, lm_head_bias=True),
    "gpt-neox-20b": _p(vocab_size=50432, hidden_size=6144, intermediate_size=24576,
                       num_layers=44, num_heads=64, max_seq_len=2048,
                       norm_type="layernorm", mlp_type="mlp",
                       activation="gelu_exact",  # HF hidden_act="gelu" = erf
                       use_bias=True, rotary_pct=0.25, parallel_block=True),
    "gptj-6b": _p(vocab_size=50400, hidden_size=4096, intermediate_size=16384,
                  num_layers=28, num_heads=16, max_seq_len=2048,
                  norm_type="layernorm", mlp_type="mlp", activation="gelu",
                  use_bias=True, qkv_bias=False, attn_out_bias=False,
                  rotary_pct=0.25, parallel_block=True, shared_block_norm=True,
                  lm_head_bias=True),
    # Llama-2 family (FastGen/ZeRO baselines; blogs/deepspeed-fastgen/README.md:135)
    "llama2-7b": _p(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                    num_layers=32, num_heads=32, num_kv_heads=32, max_seq_len=4096),
    "llama2-13b": _p(vocab_size=32000, hidden_size=5120, intermediate_size=13824,
                     num_layers=40, num_heads=40, num_kv_heads=40, max_seq_len=4096),
    "llama2-70b": _p(vocab_size=32000, hidden_size=8192, intermediate_size=28672,
                     num_layers=80, num_heads=64, num_kv_heads=8, max_seq_len=4096),
    "mistral-7b": _p(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                     num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                     sliding_window=4096),
    "mixtral-8x7b": _p(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                       num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                       num_experts=8, num_experts_per_tok=2),
    # allenai/OLMoE-1B-7B-0125-Instruct (arXiv:2409.02060): intermediate_size
    # is ONE expert's width; no shared expert, top-8 weights not renormalised
    "olmoe-1b-7b": _p(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                      num_layers=16, num_heads=16, num_kv_heads=16,
                      max_seq_len=4096, num_experts=64, num_experts_per_tok=8,
                      norm_topk_prob=False, qk_norm=True),
    # XingChen-AGI/Xing4.0-29B-A4B (model_type xing4_0): latent attention,
    # four residual streams (mHC), two leading dense layers, then 64 routed
    # experts top-4 by sigmoid scores + one shared expert. head_dim is the
    # q/k head (128 un-rotated + 64 rotated). The multi-token-prediction
    # block (num_nextn_predict_layers 1) is not part of the trunk's forward
    # and has no field here. Serving only (inference/v2).
    "xing4-29b-a4b": _p(
        vocab_size=131072, hidden_size=3584, intermediate_size=9216,
        num_layers=40, num_heads=32, num_kv_heads=32, head_dim=192,
        max_seq_len=262144, rms_norm_eps=1e-6, rope_theta=10000.0,
        kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        num_experts=64, num_experts_per_tok=4, moe_intermediate_size=1024,
        first_k_dense_replace=2, n_shared_experts=1, scoring_func="sigmoid",
        topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20,
        hc_eps=1e-6, mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0),
    # deepseek-ai/DeepSeek-V2 (model_type deepseek_v2, arXiv:2405.04434):
    # latent attention at 128 heads, one leading dense layer, then 160
    # routed experts in 8 groups, top-6 within the best 3 groups by softmax
    # scores, not renormalised, x 16, beside two shared experts (one SwiGLU
    # of twice the width). Serving only (inference/v2); a chip of an
    # expert-parallel deployment overrides num_experts_held.
    "deepseek-v2": _p(
        vocab_size=102400, hidden_size=5120, intermediate_size=12288,
        num_layers=60, num_heads=128, num_kv_heads=128, head_dim=192,
        max_seq_len=163840, rms_norm_eps=1e-6, rope_theta=10000.0,
        kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096},
        num_experts=160, num_experts_per_tok=6, moe_intermediate_size=1536,
        first_k_dense_replace=1, n_shared_experts=2, scoring_func="softmax",
        topk_method="group_limited_greedy", n_group=8, topk_group=3,
        norm_topk_prob=False, routed_scaling_factor=16.0,
        # by a sweep on the v5e (PERF.md section 6, PR 33): at 1/25 a router
        # near-tie that bf16 breaks the other way moves a row of logits by
        # up to ~0.07 logit-std, the routed experts left out by 0.15-0.4
        routed_write_share=0.04),
    # nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (model_type nemotron_h): 52
    # layers of ONE mixer each: 23 Mamba-2 (64 heads of 64, 8 groups of
    # state 128, conv 4), 23 of 128 routed relu^2 experts (two matrices, top-6
    # by sigmoid scores + selection bias, renormalised, x 2.5) beside one
    # shared expert of twice the width, 6 of GQA 32/2 attention with no
    # positional encoding. Serving only (inference/v2); a chip of an
    # expert-parallel deployment overrides num_experts_held.
    "nemotron-3-nano": _p(
        vocab_size=131072, hidden_size=2688, intermediate_size=1856,
        num_layers=52, num_heads=32, num_kv_heads=2, head_dim=128,
        max_seq_len=262144, rms_norm_eps=1e-5, pos_embed="none",
        layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
        ssm_n_groups=8, ssm_conv_kernel=4, ssm_chunk_size=128,
        activation="relu2", mlp_type="mlp",
        num_experts=128, num_experts_per_tok=6, moe_intermediate_size=1856,
        n_shared_experts=1, shared_expert_intermediate_size=3712,
        scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=2.5,
        # as deepseek-v2's: how much of a logit the seeded routed experts
        # carry (benchmark/configs/nemotron3-nano-ep4-d26.json, assumed;
        # PERF.md section 6, PR 37: by a sweep on the chip; at 1/20 a program
        # without the x 2.5 passed parity, at 1/10 router near-ties that
        # bf16 breaks the other way bring a sound run to 0.09 of its 0.1)
        routed_write_share=0.075),
    # ByteDance/Ouro-2.6B (model_type ouro, arXiv:2510.25741): 48 layers of
    # MHA 16 x 128 and SwiGLU 5632 run FOUR times over shared weights, each
    # sublayer's output normed before it joins the stream, the final norm
    # between passes, an exit gate after each. Serving only (inference/v2).
    "ouro-2.6b": _p(
        vocab_size=49152, hidden_size=2048, intermediate_size=5632,
        num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=65536, rms_norm_eps=1e-6, rope_theta=1000000.0,
        total_ut_steps=4, early_exit_threshold=1.0, sandwich_norm=True),
    # Kwai-Keye/Keye-VL-2.0-30B-A3B (model_type KeyeVL2), the language
    # model: 48 alike layers of GQA 32/4 x 128 with an RMSNorm per head on q
    # and k, a sparse-attention indexer (16 heads of 64, the best 2048 cached
    # tokens a query) and 128 SwiGLU experts of 768, top-8 by softmax
    # renormalised, no shared expert; intermediate_size 6144 is the width of
    # a dense MLP no layer has (mlp_only_layers []). The vision tower and
    # the three position rows of M-RoPE are not here: text positions, whose
    # three rows are equal, rotate as plain rotary. Serving only
    # (inference/v2); a chip of an expert-parallel deployment overrides
    # num_experts_held.
    "keye-vl2-30b-a3b": _p(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
        max_seq_len=262144, rms_norm_eps=1e-6, rope_theta=10000000.0,
        qk_head_norm=True, index_topk=2048, index_heads=16,
        index_head_dim=64,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True,
        # as deepseek-v2's: how much of a logit the seeded routed experts
        # carry (there is no shared expert: at 1 the experts' write is the
        # layer's whole MLP). By a sweep on the chip (PERF.md section 6, PR
        # 45): the router's 128 noise logits leave 14 % of the positions
        # with an 8th and 9th probability within bf16's rounding, and where
        # that flips a HELD expert the row moves by that expert's whole
        # write: at 1 the worst of 256 rows read 0.16-0.21 of the 0.1
        # allowed, at 1/4 0.086, at 1/10 0.065 (the median row 0.036 at
        # either: what is left is not the router's). The attention behind
        # the selection is seeded by a rule of its own, not a knob:
        # models/transformer.py:SELECTED_ATTN_WRITE
        routed_write_share=0.1),
    # zai-org/GLM-5 (model_type glm_moe_dsa): 78 layers of latent attention
    # at 64 heads (192 un-rotated + 64 rotated a q/k head, values of 256,
    # plain rotary at theta 1e6) under a sparse-attention indexer (32 heads
    # of 128 whose queries come of the QUERY latent and whose first 64 dims
    # rotate, a LayerNorm on its one key; the best 2048 cached tokens a
    # query); three leading dense layers, then 256 SwiGLU experts of 2048,
    # top-8 by sigmoid scores + selection bias, renormalised, x 2.5, beside
    # one shared expert. The multi-token-prediction block
    # (num_nextn_predict_layers 1) is not part of the trunk's forward and
    # has no field here. Serving only (inference/v2); a chip of an
    # expert-parallel deployment overrides num_experts_held.
    "glm-5": _p(
        vocab_size=154880, hidden_size=6144, intermediate_size=12288,
        num_layers=78, num_heads=64, num_kv_heads=64, head_dim=256,
        max_seq_len=202752, rms_norm_eps=1e-5, rope_theta=1000000.0,
        kv_lora_rank=512, q_lora_rank=2048, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256,
        index_topk=2048, index_heads=32, index_head_dim=128,
        index_rope_dim=64, index_q_latent=True,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        first_k_dense_replace=3, n_shared_experts=1, scoring_func="sigmoid",
        topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=2.5,
        # as nemotron-3-nano's, whose router this is at twice the width
        # (sigmoid + selection bias, renormalised, x 2.5, one shared
        # expert): how much of a logit the seeded routed experts carry. By
        # a sweep on the v5e (benchmark/configs/glm-5-ep16-d5.json,
        # assumed.weights; PERF.md section 6, PR 65): the sigmoid of a
        # noise logit of std 1.6 saturates, 80 % of the (position, layer)
        # pairs have an 8th and 9th score within bf16's rounding, and the
        # worst of 141 rows read 0.102 with the routed experts mute, 0.118
        # at 1/50 and 0.140 at Nemotron's 0.075, of the 0.1 allowed. The
        # attention behind the selection is seeded by a rule of its own:
        # models/transformer.py:SELECTED_LATENT_WRITE
        routed_write_share=0.02),
    # manifestai/Brumby-14B-Base (model_type brumby, arXiv:2507.04239): the
    # Qwen3-14B block (GQA 40/8 x 128, an RMSNorm per head on q and k,
    # SwiGLU 17408, untied head) retrained with power retention of degree 2
    # in the place of softmax attention: 40 alike layers, each keeping a
    # gated float32 state of [128, 8320] a KV head and sequence, none
    # caching a key. Serving only (inference/v2, ops/retention.py).
    "brumby-14b": _p(
        vocab_size=151936, hidden_size=5120, intermediate_size=17408,
        num_layers=40, num_heads=40, num_kv_heads=8, head_dim=128,
        max_seq_len=32768, rms_norm_eps=1e-6, rope_theta=1000000.0,
        qk_head_norm=True, retention_degree=2),
    # CohereLabs/command-a-plus-05-2026 (model_type cohere2_moe), the
    # language model: 32 layers in periods of three windowed (4,096 keys,
    # rotary at theta 50000 over all 128 dims) and one full layer with NO
    # positional term, GQA 128/8 x 128, under ONE bias-free LayerNorm a
    # layer that feeds attention and experts alike (parallel block); 128
    # SwiGLU experts of 4096, top-8 by sigmoid renormalised, beside four
    # shared experts whose MEAN is added (one 16,384-wide GLU scaled by
    # 1/4); tied embedding, logit_scale 1, no leading dense layer. The
    # published rotary pairs are interleaved (rope_gptj); the program keeps
    # its split-half apply_rope, which is the same function of weights
    # whose q/k columns are permuted (models/layers.py:apply_rope). The
    # vision tower is not here. Serving only (inference/v2); a chip of an
    # expert-parallel deployment overrides num_experts_held.
    "command-a-plus": _p(
        vocab_size=262144, hidden_size=4096, intermediate_size=4096,
        num_layers=32, num_heads=128, num_kv_heads=8, head_dim=128,
        max_seq_len=200000, rms_norm_eps=1e-5, rope_theta=50000.0,
        norm_type="layernorm", norm_bias=False, tie_embeddings=True,
        logit_scale=1.0, parallel_block=True, shared_block_norm=True,
        attn_period=((4096, "rope"),) * 3 + ((None, "none"),),
        num_experts=128, num_experts_per_tok=8, norm_topk_prob=True,
        scoring_func="sigmoid", n_shared_experts=4,
        shared_expert_combine="average",
        # as deepseek-v2's: how much of a logit the seeded routed experts
        # carry beside the shared ones, so that parity can see them
        routed_write_share=0.05),
    # upstage/Solar-Open2-250B (model_type solar_open2, 250B-A15B): 48
    # published layers, each a mixer AND an expert block, so 96 characters
    # here: 12 layers of GQA 64/8 x 128 with NO positional term and an
    # output gate (gqa_layers 0, 4, ..., 44: ``*E``), 36 of the gated delta
    # rule (64 heads of 128, conv 4, a decay per key channel and an output
    # gate through rank-128 pairs, beta up to 2: ``KE``), every one followed
    # by 320 SwiGLU experts of 1280, top-8 by sigmoid scores with a
    # selection bias, renormalised, x 1, beside one shared expert;
    # intermediate_size 10240 is the width of a dense MLP no layer has
    # (first_k_dense_replace 0). Serving only (inference/v2, ops/kda.py); a
    # chip of an expert-parallel deployment overrides num_experts_held.
    "solar-open2": _p(
        vocab_size=196608, hidden_size=4096, intermediate_size=10240,
        num_layers=96, num_heads=64, num_kv_heads=8, head_dim=128,
        max_seq_len=1048576, rms_norm_eps=1e-5, pos_embed="none",
        layer_pattern="*EKEKEKE" * 12, attn_out_gate=True,
        kda_num_heads=64, kda_head_dim=128, kda_conv_kernel=4,
        kda_gate_rank=128, kda_beta_scale=2.0, kda_chunk_size=64,
        num_experts=320, num_experts_per_tok=8, moe_intermediate_size=1280,
        n_shared_experts=1, scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=True, routed_scaling_factor=1.0,
        # as deepseek-v2's: how much of a logit the seeded routed experts
        # carry beside the shared one, so that parity can see them
        # (benchmark/configs/solar-open2-ep8-d4.json, assumed)
        routed_write_share=0.075),
    # openbmb/MiniCPM-SALA (model_type minicpm_sala, 9B): 32 published
    # layers, each a mixer AND a dense SwiGLU MLP of 16,384, so 64
    # characters here: 8 layers of InfLLM-v2 block-sparse attention
    # (mixer_types "minicpm4" at 0, 9, 16, 17, 22, 29, 30, 31: 32 heads over
    # 2 KV heads of 128, normed a head, NO positional term, an output gate:
    # ``*F``) and 24 of lightning linear attention (32 heads of 128, normed
    # a head and rotated, output norm and gate: ``LF``); muP: the embedding
    # x 12, every sublayer x 1.4 / sqrt(32), the logits / (4096 / 256).
    # Serving only (inference/v2, ops/sparse_block.py, ops/ssm.py).
    "minicpm-sala": _p(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        num_layers=64, num_heads=32, num_kv_heads=2, head_dim=128,
        max_seq_len=524288, rms_norm_eps=1e-6, rope_theta=10000.0,
        pos_embed="none", tie_embeddings=False, qk_head_norm=True,
        layer_pattern="".join(
            ("*" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "L") + "F"
            for i in range(32)),
        attn_out_gate=True, lightning_heads=32, lightning_head_dim=128,
        sparse_block_topk=96, sparse_block_size=64, sparse_block_kernel=32,
        sparse_block_stride=16, sparse_block_init=1, sparse_block_window=32,
        sparse_block_dense_len=8192,
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
        logit_scale=256 / 4096),
    # tiiuae/Falcon-H1-34B-Instruct (model_type falcon_h1): 72 published
    # layers, each attention heads (GQA 20/4 x 128, rotary at theta 1e11) AND
    # a Mamba-2 mixer (32 heads of 128 = mamba_d_ssm 4096, 2 groups of state
    # 256, conv 4 with bias, the gate before the grouped norm) side by side
    # behind ONE norm, their outputs summed into one residual add (``H``),
    # then a SwiGLU MLP of 21,504 (``F``): 144 characters here. muP: the
    # embedding x 5.657, the logits x 2^-7 and twelve multipliers inside the
    # layer (``mup``). Serving only (inference/v2, ops/ssm.py).
    "falcon-h1-34b": _p(
        vocab_size=261120, hidden_size=5120, intermediate_size=21504,
        num_layers=144, num_heads=20, num_kv_heads=4, head_dim=128,
        max_seq_len=262144, rms_norm_eps=1e-5, rope_theta=1e11,
        layer_pattern="HF" * 72,
        mamba_num_heads=32, mamba_head_dim=128, ssm_state_size=256,
        ssm_n_groups=2, ssm_conv_kernel=4, ssm_chunk_size=128,
        embed_scale=5.656854249492381, logit_scale=0.0078125,
        mup=MuP(attention_in=1.0, attention_out=0.0375,
                key=0.011048543456039804, ssm_in=0.25,
                ssm_out=0.08838834764831845,
                ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
                mlp=(0.1767766952966369, 0.011160714285714284))),
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    return replace(PRESETS[name], **overrides) if overrides else PRESETS[name]
