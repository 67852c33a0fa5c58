"""JSON config system.

Analog of ``deepspeed/runtime/config.py`` (``DeepSpeedConfig``) +
``runtime/config_utils.py`` + the per-subsystem pydantic models
(``runtime/zero/config.py``, ``monitor/config.py``, ``comm/config.py`` …).

Same surface: one JSON file or dict drives the whole engine; the batch invariant
``train_batch_size = micro_batch_per_device × gradient_accumulation_steps ×
dp_world_size`` is enforced/derived exactly like the reference's
``_batch_assertion``/``_set_batch_related_parameters`` logic. Implementation is plain
dataclasses — no pydantic dependency — because the schema is small and static.
"""
import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from . import constants as C
from .offload_pipeline import DEFAULT_BUCKET_BYTES
from ..utils.logging import logger

AUTO = "auto"


def _sub(d: Dict[str, Any], key: str) -> Dict[str, Any]:
    v = d.get(key, {})
    if v in (None, False):
        return {}
    if v is True:
        return {"enabled": True}
    if not isinstance(v, dict):
        raise ValueError(f"config section {key!r} must be a dict, got {type(v)}")
    return v


@dataclass
class OptimizerConfig:
    """``optimizer`` section (reference: ``_configure_basic_optimizer``,
    ``engine.py:1267`` — Adam/AdamW/Lamb/OneBitAdam/Lion via op builders; ours map
    to optax transforms, fused by XLA)."""
    type: str = C.OPTIMIZER_TYPE_DEFAULT
    params: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "OptimizerConfig":
        return cls(type=str(d.get("type", C.OPTIMIZER_TYPE_DEFAULT)).lower(),
                   params=dict(d.get("params", {})))

    @property
    def lr(self) -> float:
        return float(self.params.get("lr", 1e-3))


@dataclass
class SchedulerConfig:
    """``scheduler`` section (reference: ``runtime/lr_schedules.py``)."""
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SchedulerConfig":
        return cls(type=d.get("type"), params=dict(d.get("params", {})))


@dataclass
class Fp16Config:
    """``fp16`` section incl. dynamic loss scaling knobs
    (reference: ``runtime/fp16/loss_scaler.py`` DynamicLossScaler)."""
    enabled: bool = False
    loss_scale: float = 0.0  # 0 → dynamic
    initial_scale_power: int = C.INITIAL_LOSS_SCALE_POWER_DEFAULT
    loss_scale_window: int = C.LOSS_SCALE_WINDOW_DEFAULT
    hysteresis: int = C.HYSTERESIS_DEFAULT
    min_loss_scale: float = C.MIN_LOSS_SCALE_DEFAULT

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Fp16Config":
        return cls(enabled=bool(d.get("enabled", False)),
                   loss_scale=float(d.get("loss_scale", 0.0)),
                   initial_scale_power=int(d.get(C.INITIAL_LOSS_SCALE_POWER,
                                                 C.INITIAL_LOSS_SCALE_POWER_DEFAULT)),
                   loss_scale_window=int(d.get(C.LOSS_SCALE_WINDOW,
                                               C.LOSS_SCALE_WINDOW_DEFAULT)),
                   hysteresis=int(d.get(C.HYSTERESIS, C.HYSTERESIS_DEFAULT)),
                   min_loss_scale=float(d.get(C.MIN_LOSS_SCALE,
                                              C.MIN_LOSS_SCALE_DEFAULT)))

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == 0.0

    @property
    def initial_scale(self) -> float:
        return float(self.loss_scale) if self.loss_scale else 2.0 ** self.initial_scale_power


@dataclass
class Bf16Config:
    enabled: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Bf16Config":
        return cls(enabled=bool(d.get("enabled", False)))


@dataclass
class OffloadConfig:
    """``zero_optimization.offload_{optimizer,param}`` (reference:
    ``runtime/zero/offload_config.py``). ``device`` 'cpu' = host RAM via
    jax.device_put to the host backend; 'nvme' = async file swap (csrc/aio analog).

    Pipeline knobs (``runtime/offload_pipeline.py`` — see docs/offload.md):
    ``pipeline`` routes Adam-family offload through the bucketed D2H /
    host-Adam / H2D pipeline (reference ``offload_config.py`` carries the
    same flag name for its overlapped swap path); ``bucket_size`` is the
    size-targeted transfer/compute unit in bytes (small leaves coalesce);
    ``buffer_count`` is the NVMe moment-window depth in buckets (the
    reference's aio buffer_count — host RAM for moments is bounded by this
    window, not the store); ``overlap`` off runs identical math inline
    (the bit-parity debug arm)."""
    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    pin_memory: bool = True
    pipeline: bool = True
    bucket_size: int = DEFAULT_BUCKET_BYTES
    buffer_count: int = 2
    overlap: bool = True

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "OffloadConfig":
        bucket = int(d.get("bucket_size", DEFAULT_BUCKET_BYTES))
        buffers = int(d.get("buffer_count", 2))
        if bucket <= 0:
            raise ValueError(
                f"offload bucket_size must be > 0 bytes, got {bucket}")
        if buffers < 1:
            raise ValueError(
                f"offload buffer_count must be >= 1, got {buffers}")
        return cls(device=str(d.get("device", "none")),
                   nvme_path=d.get("nvme_path"),
                   pin_memory=bool(d.get("pin_memory", True)),
                   pipeline=bool(d.get("pipeline", True)),
                   bucket_size=bucket,
                   buffer_count=buffers,
                   overlap=bool(d.get("overlap", True)))

    @property
    def enabled(self) -> bool:
        return self.device not in ("none", None)


@dataclass
class ZeroConfig:
    """``zero_optimization`` section (reference: ``runtime/zero/config.py``
    ``DeepSpeedZeroConfig``). Stages keep reference semantics:

    0 → pure DP (replicated params/opt, psum grads)         [engine.py:1903]
    1 → optimizer state sharded over fsdp axis              [stage_1_and_2.py]
    2 → + gradient shards (reduce_scatter at boundary)      [stage_1_and_2.py:1004]
    3 → + parameter shards (XLA all-gathers per use)        [stage3.py]

    ZeRO++ knobs map to quantized-collective / hierarchical-partition analogs.
    """
    stage: int = C.ZERO_STAGE_DEFAULT
    offload_optimizer: OffloadConfig = field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = field(default_factory=OffloadConfig)
    zero_quantized_weights: bool = False    # qwZ: int8 weight all-gather
    zero_quantized_gradients: bool = False  # qgZ: int8 grad reduce
    zero_hpz_partition_size: int = 1        # hpZ: secondary shard group size
    mics_shard_size: int = -1               # MiCS: sub-world shard groups
    overlap_comm: bool = True
    contiguous_gradients: bool = True
    reduce_bucket_size: int = 5 * 10**8

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ZeroConfig":
        stage = int(d.get(C.ZERO_STAGE, C.ZERO_STAGE_DEFAULT))
        if stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_optimization.stage must be 0-3, got {stage}")
        return cls(
            stage=stage,
            offload_optimizer=OffloadConfig.from_dict(_sub(d, C.OFFLOAD_OPTIMIZER)),
            offload_param=OffloadConfig.from_dict(_sub(d, C.OFFLOAD_PARAM)),
            zero_quantized_weights=bool(d.get("zero_quantized_weights", False)),
            zero_quantized_gradients=bool(d.get("zero_quantized_gradients", False)),
            zero_hpz_partition_size=int(d.get("zero_hpz_partition_size", 1)),
            mics_shard_size=int(d.get("mics_shard_size", -1)),
            overlap_comm=bool(d.get("overlap_comm", True)),
            contiguous_gradients=bool(d.get("contiguous_gradients", True)),
            reduce_bucket_size=int(d.get("reduce_bucket_size", 5 * 10**8)),
        )


@dataclass
class ParallelismConfig:
    """Mesh axis sizes. dstpu-native section; also populated from reference-style
    sections (``tensor_parallel.tp_size``, ``pipeline.stages``,
    ``sequence_parallel_size``, ``moe.expert_parallel_size``) for config parity."""
    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    # pipeline microbatches per forward (pipeline.micro_batches; None =>
    # one per stage) — reference PipelineEngine streams GAS microbatches
    pp_microbatches: Optional[int] = None

    @classmethod
    def from_config_dict(cls, d: Dict[str, Any], zero_stage: int,
                         mics_shard_size: int = -1) -> "ParallelismConfig":
        p = _sub(d, C.PARALLELISM)
        tp = int(p.get("tp", _sub(d, C.TENSOR_PARALLEL).get("tp_size", 1)))
        pipe_sec = _sub(d, C.PIPELINE)
        pp = int(p.get("pp", pipe_sec.get("stages", 1)))
        pp_micro = pipe_sec.get("micro_batches")
        pp_micro = int(pp_micro) if pp_micro is not None else None
        ep = int(p.get("ep", _sub(d, C.MOE).get("expert_parallel_size", 1)))
        sp = int(p.get("sp", d.get(C.SEQUENCE_PARALLEL_SIZE, 1)))
        fsdp = int(p.get("fsdp", 0)) or 0
        dp = int(p.get("dp", 0)) or 0
        if mics_shard_size and mics_shard_size > 0:
            # MiCS (reference runtime/zero/mics.py MiCS_Init): ZeRO shard
            # groups smaller than the world — partition within an fsdp axis
            # of exactly the shard-group size, replicate across the data
            # axis. The reference's hierarchical allgather falls out of the
            # axis order (fsdp is ICI-inner; data crosses the slower tier).
            if fsdp and fsdp != mics_shard_size:
                raise ValueError(
                    f"mics_shard_size {mics_shard_size} conflicts with "
                    f"explicit fsdp={fsdp}")
            fsdp, dp = mics_shard_size, (dp or -1)
        elif not fsdp and not dp:
            # ZeRO>=1 shards over fsdp: default puts all data-parallel replicas on
            # the fsdp axis; plain DP keeps them on data.
            if zero_stage >= 1:
                fsdp, dp = -1, 1
            else:
                dp, fsdp = -1, 1
        elif not fsdp:
            fsdp = 1
        elif not dp:
            dp = 1
        return cls(dp=dp, fsdp=fsdp, tp=tp, pp=pp, ep=ep, sp=sp,
                   pp_microbatches=pp_micro)


@dataclass
class ActivationCheckpointingConfig:
    """``activation_checkpointing`` (reference:
    ``runtime/activation_checkpointing/checkpointing.py``). Under XLA this maps to
    ``jax.checkpoint`` policies rather than manual save/recompute: the section
    puts ``jax.checkpoint`` around every layer of the model's stack, and
    ``policy`` says what a layer keeps for its backward pass.

    WITHOUT ``policy`` the backward keeps what the device has room for: the
    model's step takes the richest rung of ``models/remat.py`` whose saved
    bytes, over all layers and on one device, fit in the device's memory
    (``memory_stats()["bytes_limit"]`` less 5 %) less the engine's resident
    state (parameters, optimizer state, float32 gradients) and the step's
    counted working set. The rungs and their bytes a layer (bf16, ``T`` tokens
    a device, widths ``d`` / ``q`` / ``kv`` / ``f``, ``h`` heads):

    * ``attn+mlp``: the q / k / v projections, the attention sublayer's
      output, the flash kernel's ``o`` and ``lse``, the MLP's gate and up
      products: ``T x (4q + 4kv + 4h + 2d + 4f)`` B (0.71 GB at mistral-7b's
      widths and T = 8,192); only norms, rotary and the activation are
      recomputed;
    * ``attn``: the attention sublayer's alone, ``T x (4q + 4kv + 4h + 2d)``
      B (0.24 GB);
    * ``nothing_saveable``: the layer's input alone, everything recomputed.
      Taken where the device reports no limit (the CPU).

    The engine logs the rung and its bytes at the step's first trace and
    publishes them with the compiled step (``Engine.remat_choice``,
    ``monitor.mfu.step_record``). If the compiled step still does not fit,
    the engine steps down one rung, says so, and compiles again.

    WITH ``policy`` (one of ``VALID_POLICIES``), or with
    ``cpu_checkpointing``, the section means exactly that policy.

    A pipelined trunk (``pipeline.stages > 1``) and random-LTD's middle stack
    checkpoint whole layers whatever the policy."""
    # section presence turns checkpointing ON unless explicitly disabled
    # ("enabled" is a dstpu extension: the reference has no off-switch in
    # the section, and partition_activations means TP-partitioning there,
    # NOT enablement — ported configs with partition_activations=false
    # still expect remat on)
    enabled: bool = True
    partition_activations: bool = False
    number_checkpoints: Optional[int] = None
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    policy: Optional[str] = None  # jax.checkpoint policy name; None: by room

    # zero-arg jax.checkpoint_policies only — factory-style names (e.g.
    # save_only_these_names) would be silently misused as policies
    VALID_POLICIES = ("nothing_saveable", "everything_saveable",
                      "dots_saveable", "checkpoint_dots",
                      "offload_dots_to_host",
                      "dots_with_no_batch_dims_saveable",
                      "checkpoint_dots_with_no_batch_dims")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ActivationCheckpointingConfig":
        policy = d.get("policy")
        if policy is not None and policy not in cls.VALID_POLICIES:
            raise ValueError(
                f"activation_checkpointing.policy {policy!r} is not a "
                f"supported jax.checkpoint policy; choose one of "
                f"{cls.VALID_POLICIES}")
        return cls(enabled=bool(d.get("enabled", True)),
                   partition_activations=bool(d.get("partition_activations", False)),
                   number_checkpoints=d.get("number_checkpoints"),
                   contiguous_memory_optimization=bool(
                       d.get("contiguous_memory_optimization", False)),
                   cpu_checkpointing=bool(d.get("cpu_checkpointing", False)),
                   policy=policy)


@dataclass
class MonitorConfig:
    """``tensorboard``/``wandb``/``csv_monitor``/``jsonl_monitor`` sections
    (reference: ``monitor/config.py``; jsonl is the rank-local flight-recorder
    sink, see ``monitor/telemetry.py``)."""
    tensorboard_enabled: bool = False
    tensorboard_output_path: str = ""
    tensorboard_job_name: str = "DSTpuJobName"
    wandb_enabled: bool = False
    wandb_project: Optional[str] = None
    wandb_team: Optional[str] = None
    wandb_group: Optional[str] = None
    csv_enabled: bool = False
    csv_output_path: str = ""
    csv_job_name: str = "DSTpuJobName"
    csv_flush_interval: int = 10  # write batches between csv flushes
    jsonl_enabled: bool = False
    jsonl_output_path: str = ""
    jsonl_job_name: str = "DSTpuJobName"
    jsonl_flush_interval: int = 64  # records buffered between jsonl flushes

    @classmethod
    def from_config_dict(cls, d: Dict[str, Any]) -> "MonitorConfig":
        tb = _sub(d, C.MONITOR_TENSORBOARD)
        wb = _sub(d, C.MONITOR_WANDB)
        csv = _sub(d, C.MONITOR_CSV)
        jl = _sub(d, C.MONITOR_JSONL)
        return cls(
            tensorboard_enabled=bool(tb.get("enabled", False)),
            tensorboard_output_path=tb.get("output_path", ""),
            tensorboard_job_name=tb.get("job_name", "DSTpuJobName"),
            wandb_enabled=bool(wb.get("enabled", False)),
            wandb_project=wb.get("project"),
            wandb_team=wb.get("team"),
            wandb_group=wb.get("group"),
            csv_enabled=bool(csv.get("enabled", False)),
            csv_output_path=csv.get("output_path", ""),
            csv_job_name=csv.get("job_name", "DSTpuJobName"),
            csv_flush_interval=int(csv.get("flush_interval", 10)),
            jsonl_enabled=bool(jl.get("enabled", False)),
            jsonl_output_path=jl.get("output_path", ""),
            jsonl_job_name=jl.get("job_name", "DSTpuJobName"),
            jsonl_flush_interval=int(jl.get("flush_interval", 64)),
        )

    @property
    def enabled(self) -> bool:
        return (self.tensorboard_enabled or self.wandb_enabled
                or self.csv_enabled or self.jsonl_enabled)


@dataclass
class TelemetryConfig:
    """``telemetry`` section — the structured observability layer
    (``monitor/telemetry.py``): flight recorder + rank-local JSONL, goodput
    accounting, recompile detection, HBM gauges, heartbeat file and
    on-demand ``jax.profiler`` trace windows. ``DSTPU_TELEMETRY=1`` forces
    ``enabled`` at runtime without a config edit."""
    enabled: bool = False
    output_dir: str = "telemetry_logs"
    ring_size: int = 4096
    flush_interval_records: int = 64
    memory_interval_steps: int = 10
    heartbeat_enabled: bool = True
    heartbeat_interval_s: float = 1.0
    stack_dump_on_hang: bool = True
    goodput_enabled: bool = True
    # block on the step's outputs before timing it: device-accurate step
    # spans, at the cost of the host/device dispatch overlap
    sync_timing: bool = False
    # Prometheus textfile-collector snapshot (metrics_rank<N>.prom,
    # atomic rename) refreshed at heartbeat cadence — long multi-host runs
    # are scraped off this file instead of anyone tailing JSONL
    textfile_enabled: bool = False
    textfile_interval_s: float = 15.0
    # Collective hang watchdog (comm/watchdog.py): the engine arms a
    # deadline around each step's collective dispatch; on expiry the
    # watchdog thread dumps stacks, flushes the recorder and exits rc 218
    # (the comm-hang contract the elastic agent restarts distinctly).
    # warmup_deadline_s covers the first (compiling) step; None = 10x.
    watchdog_enabled: bool = False
    watchdog_deadline_s: float = 60.0
    watchdog_warmup_deadline_s: Optional[float] = None
    watchdog_poll_s: float = 0.25
    trace_start_step: Optional[int] = None
    trace_num_steps: int = 3
    trace_dir: Optional[str] = None
    # MFU ledger (monitor/mfu.py + analysis/roofline.py): auto-capture ONE
    # jax.profiler window around a clean (non-compiling) step — earliest at
    # mfu_step — and join it against the roofline partition via
    # Engine.mfu_ledger(). The window costs one synced step; everything
    # else is offline.
    mfu_enabled: bool = False
    mfu_step: int = 3

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TelemetryConfig":
        hb = dict(d.get("heartbeat", {}))
        tr = dict(d.get("trace", {}))
        tf = dict(d.get("textfile", {}))
        wd = dict(d.get("watchdog", {}))
        mfu = dict(d.get("mfu", {}))
        mfu_step = int(mfu.get("step", 3))
        if mfu_step < 1:
            raise ValueError(f"telemetry.mfu.step must be >= 1, got "
                             f"{mfu_step} (step 1 includes the first "
                             f"compile; the capture skips compiling steps "
                             f"anyway)")
        ring = int(d.get("ring_size", 4096))
        if ring <= 0:
            raise ValueError(f"telemetry.ring_size must be > 0, got {ring}")
        tf_interval = float(tf.get("interval_s", 15.0))
        if tf_interval <= 0:
            raise ValueError(f"telemetry.textfile.interval_s must be > 0, "
                             f"got {tf_interval}")
        wd_deadline = float(wd.get("deadline_s", 60.0))
        wd_poll = float(wd.get("poll_s", 0.25))
        if wd_deadline <= 0 or wd_poll <= 0:
            raise ValueError(
                f"telemetry.watchdog deadline_s/poll_s must be > 0, got "
                f"{wd_deadline}/{wd_poll}")
        wd_warmup = wd.get("warmup_deadline_s")
        if wd_warmup is not None and float(wd_warmup) < wd_deadline:
            raise ValueError(
                f"telemetry.watchdog.warmup_deadline_s ({wd_warmup}) must "
                f"cover at least deadline_s ({wd_deadline}) — the first "
                f"armed step includes compilation")
        start = tr.get("start_step")
        return cls(
            enabled=bool(d.get("enabled", False)),
            output_dir=str(d.get("output_dir", "telemetry_logs")),
            ring_size=ring,
            flush_interval_records=int(d.get("flush_interval_records", 64)),
            memory_interval_steps=int(d.get("memory_interval_steps", 10)),
            heartbeat_enabled=bool(hb.get("enabled", True)),
            heartbeat_interval_s=float(hb.get("interval_s", 1.0)),
            stack_dump_on_hang=bool(d.get("stack_dump_on_hang", True)),
            sync_timing=bool(d.get("sync_timing", False)),
            textfile_enabled=bool(tf.get("enabled", False)),
            textfile_interval_s=tf_interval,
            watchdog_enabled=bool(wd.get("enabled", False)),
            watchdog_deadline_s=wd_deadline,
            watchdog_warmup_deadline_s=(None if wd_warmup is None
                                        else float(wd_warmup)),
            watchdog_poll_s=wd_poll,
            goodput_enabled=bool(d.get("goodput", {}).get("enabled", True)
                                 if isinstance(d.get("goodput"), dict)
                                 else d.get("goodput", True)),
            trace_start_step=None if start is None else int(start),
            trace_num_steps=int(tr.get("num_steps", 3)),
            trace_dir=tr.get("trace_dir"),
            mfu_enabled=bool(mfu.get("enabled", False)),
            mfu_step=mfu_step,
        )


@dataclass
class SentinelConfig:
    """``sentinel`` section — the training-health sentinel
    (``runtime/sentinel.py``): in-graph NaN/spike gating piggybacked on the
    step's output fetch, host-side robust z-score detection over the
    loss/grad-norm history, and the graduated response ladder
    ``warn → skip_batch → rollback → abort`` (rc 220)."""
    enabled: bool = False
    # spike detection arms only after this many healthy steps of history —
    # early-training loss moves fast and would trip any static threshold
    warmup_steps: int = 20
    # history window for the robust (median/MAD) statistics
    window: int = 64
    # EWMA smoothing factor for the drift-following baseline
    ewma_alpha: float = 0.1
    # robust z at which an observation is a WARN (journaled, update applied)
    z_warn: float = 4.0
    # robust z at which the in-graph gate discards the update (skip_batch)
    z_skip: float = 8.0
    # consecutive anomalous steps before the ladder escalates to rollback
    skip_limit: int = 3
    # rollbacks without an intervening healthy window before abort (rc 220)
    rollback_limit: int = 2
    # healthy steps that must be observed BEYOND a saved tag before the
    # sentinel promotes it as a last-good rollback target
    last_good_k: int = 4
    # transient LR cut after a rollback: gradients are scaled by lr_cut for
    # lr_cut_steps steps (1.0 / 0 disables)
    lr_cut: float = 1.0
    lr_cut_steps: int = 0
    # decision lag in steps: verdict for step N is issued at the boundary of
    # step N+lag, when N's scalars have already materialized — the sentinel
    # never adds a blocking host sync to the step path
    lag: int = 1
    # rollback source; defaults to wherever the engine last saved
    checkpoint_dir: Optional[str] = None
    # health_journal_rank<N>.jsonl location; defaults to telemetry.output_dir
    journal_dir: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SentinelConfig":
        z_warn = float(d.get("z_warn", 4.0))
        z_skip = float(d.get("z_skip", 8.0))
        if z_skip < z_warn:
            raise ValueError(f"sentinel.z_skip ({z_skip}) must be >= z_warn "
                             f"({z_warn}) — the ladder escalates, it does "
                             f"not invert")
        lag = int(d.get("lag", 1))
        if lag < 1:
            raise ValueError(f"sentinel.lag must be >= 1, got {lag} — lag 0 "
                             f"would block the host on the in-flight step")
        for key, lo in (("warmup_steps", 1), ("window", 4),
                        ("skip_limit", 1), ("rollback_limit", 0),
                        ("last_good_k", 1), ("lr_cut_steps", 0)):
            if int(d.get(key, lo)) < lo:
                raise ValueError(f"sentinel.{key} must be >= {lo}, got "
                                 f"{d.get(key)}")
        return cls(
            enabled=bool(d.get("enabled", False)),
            warmup_steps=int(d.get("warmup_steps", 20)),
            window=int(d.get("window", 64)),
            ewma_alpha=float(d.get("ewma_alpha", 0.1)),
            z_warn=z_warn,
            z_skip=z_skip,
            skip_limit=int(d.get("skip_limit", 3)),
            rollback_limit=int(d.get("rollback_limit", 2)),
            last_good_k=int(d.get("last_good_k", 4)),
            lr_cut=float(d.get("lr_cut", 1.0)),
            lr_cut_steps=int(d.get("lr_cut_steps", 0)),
            lag=lag,
            checkpoint_dir=d.get("checkpoint_dir"),
            journal_dir=d.get("journal_dir"),
        )


@dataclass
class CommsLoggerConfig:
    """``comms_logger`` section (reference: ``comm/config.py``)."""
    enabled: bool = False
    verbose: bool = False
    debug: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "CommsLoggerConfig":
        return cls(enabled=bool(d.get("enabled", False)),
                   verbose=bool(d.get("verbose", False)),
                   debug=bool(d.get("debug", False)))


@dataclass
class FlopsProfilerConfig:
    """``flops_profiler`` section (reference: ``profiling/config.py``)."""
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FlopsProfilerConfig":
        return cls(enabled=bool(d.get("enabled", False)),
                   profile_step=int(d.get("profile_step", 1)),
                   module_depth=int(d.get("module_depth", -1)),
                   top_modules=int(d.get("top_modules", 1)),
                   detailed=bool(d.get("detailed", True)),
                   output_file=d.get("output_file"))


@dataclass
class CheckpointConfig:
    """``checkpoint`` section (reference: ``runtime/config.py`` checkpoint_config +
    tag validation collective ``engine.py:3033``)."""
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    use_node_local_storage: bool = False
    load_universal: bool = False
    # sync by default (reference: TorchCheckpointEngine); the async
    # Nebula-analog engine is opt-in via async_save or engine="async"
    async_save: bool = False
    engine: str = "native"  # native | async (checkpoint/ckpt_engine.py)
    # rotation: keep the newest N *verified* checkpoints, GC older ones after
    # each durable save (checkpoint/engine.py::rotate_checkpoints). 0 = never
    # delete anything (the default — rotation is opt-in).
    keep_last_n: int = 0

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "CheckpointConfig":
        tv = str(d.get("tag_validation", "Warn")).capitalize()
        if tv not in ("Ignore", "Warn", "Fail"):
            raise ValueError(f"checkpoint.tag_validation must be Ignore|Warn|Fail, got {tv}")
        async_save = bool(d.get("async_save", False))
        engine = str(d.get("engine", "async" if async_save else "native"))
        if engine not in ("native", "async"):
            raise ValueError(f"checkpoint.engine must be native|async, got {engine!r}")
        if "engine" in d and "async_save" in d and \
                async_save != (engine == "async"):
            raise ValueError(
                f"contradictory checkpoint config: engine={engine!r} with "
                f"async_save={async_save}")
        async_save = engine == "async"  # keep the two views consistent
        keep_last_n = int(d.get("keep_last_n", 0))
        if keep_last_n < 0:
            raise ValueError(
                f"checkpoint.keep_last_n must be >= 0, got {keep_last_n}")
        return cls(tag_validation=tv,
                   use_node_local_storage=bool(d.get("use_node_local_storage", False)),
                   load_universal=bool(d.get("load_universal", False)),
                   async_save=async_save, engine=engine,
                   keep_last_n=keep_last_n)


@dataclass
class ProgressiveLayerDropConfig:
    """``progressive_layer_drop`` section (reference:
    ``runtime/progressive_layer_drop.py``, constants PLD_*)."""
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProgressiveLayerDropConfig":
        return cls(enabled=bool(d.get("enabled", False)),
                   theta=float(d.get("theta", 0.5)),
                   gamma=float(d.get("gamma", 0.001)))


@dataclass
class DataEfficiencyConfig:
    """``data_efficiency`` section (reference:
    ``runtime/data_pipeline/config.py`` + ``constants.py`` key families),
    plus the legacy top-level ``curriculum_learning`` section. Resolved
    curriculum/random-ltd dicts feed ``runtime/data_pipeline``."""
    enabled: bool = False
    seed: int = 1234
    curriculum: Optional[Dict[str, Any]] = None      # scheduler config dict
    curriculum_metric: str = "seqlen"
    random_ltd: Optional[Dict[str, Any]] = None      # scheduler config dict

    @classmethod
    def from_config_dict(cls, d: Dict[str, Any]) -> "DataEfficiencyConfig":
        de = dict(d.get("data_efficiency", {}))
        sampling = dict(de.get("data_sampling", {}))
        routing = dict(de.get("data_routing", {}))
        curriculum = None
        metric = "seqlen"
        # nested (data_efficiency.data_sampling.curriculum_learning) …
        cl = dict(sampling.get("curriculum_learning", {}))
        if cl.get("enabled", False):
            metrics = dict(cl.get("curriculum_metrics", {}))
            if len(metrics) > 1:
                raise ValueError(
                    "multiple curriculum_metrics are not supported; "
                    f"configure exactly one (got {sorted(metrics)})")
            if metrics:  # reference: named metric sub-sections
                metric, cl = next(iter(metrics.items()))
                cl = dict(cl)
            curriculum = cl
        # … or legacy top-level curriculum_learning
        legacy = dict(d.get("curriculum_learning", {}))
        if curriculum is None and legacy.get("enabled", False):
            curriculum = legacy
            metric = legacy.get("curriculum_type", "seqlen")
        ltd = dict(routing.get("random_ltd", {}))
        random_ltd = ltd if ltd.get("enabled", False) else None
        enabled = bool(de.get("enabled", False) or curriculum is not None
                       or random_ltd is not None)
        return cls(enabled=enabled, seed=int(de.get("seed", 1234)),
                   curriculum=curriculum, curriculum_metric=metric,
                   random_ltd=random_ltd)


@dataclass
class DSTpuConfig:
    """Top-level typed config (reference: ``DeepSpeedConfig``)."""

    raw: Dict[str, Any]
    train_batch_size: int
    train_micro_batch_size_per_gpu: int
    gradient_accumulation_steps: int
    optimizer: OptimizerConfig
    scheduler: SchedulerConfig
    fp16: Fp16Config
    bf16: Bf16Config
    zero: ZeroConfig
    parallelism: ParallelismConfig
    activation_checkpointing: ActivationCheckpointingConfig
    monitor: MonitorConfig
    comms_logger: CommsLoggerConfig
    flops_profiler: FlopsProfilerConfig
    checkpoint: CheckpointConfig
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    sentinel: SentinelConfig = field(default_factory=SentinelConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = field(
        default_factory=ProgressiveLayerDropConfig)
    data_efficiency: DataEfficiencyConfig = field(
        default_factory=DataEfficiencyConfig)
    gradient_clipping: float = C.GRADIENT_CLIPPING_DEFAULT
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    steps_per_print: int = C.STEPS_PER_PRINT_DEFAULT
    wall_clock_breakdown: bool = False
    seed: int = C.SEED_DEFAULT
    dump_state: bool = False

    # ------------------------------------------------------------------ parse
    @classmethod
    def from_config(cls, config, dp_world_size: Optional[int] = None) -> "DSTpuConfig":
        if isinstance(config, (str, os.PathLike)):
            with open(config) as f:
                d = json.load(f)
        elif isinstance(config, dict):
            d = dict(config)
        elif isinstance(config, DSTpuConfig):
            return config
        else:
            raise TypeError(f"config must be dict or path, got {type(config)}")

        for key in set(d) & C.IGNORED_REFERENCE_KEYS:
            logger.warning("config key %r has no TPU analog; ignored", key)

        fp16 = Fp16Config.from_dict(_sub(d, C.FP16))
        bf16 = Bf16Config.from_dict(_sub(d, C.BF16))
        if fp16.enabled and bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")
        zero = ZeroConfig.from_dict(_sub(d, C.ZERO_OPTIMIZATION))

        cfg = cls(
            raw=d,
            train_batch_size=0,
            train_micro_batch_size_per_gpu=0,
            gradient_accumulation_steps=0,
            optimizer=OptimizerConfig.from_dict(_sub(d, C.OPTIMIZER)),
            scheduler=SchedulerConfig.from_dict(_sub(d, C.SCHEDULER)),
            fp16=fp16,
            bf16=bf16,
            zero=zero,
            parallelism=ParallelismConfig.from_config_dict(
                d, zero.stage, zero.mics_shard_size),
            activation_checkpointing=ActivationCheckpointingConfig.from_dict(
                _sub(d, C.ACTIVATION_CHECKPOINTING)),
            monitor=MonitorConfig.from_config_dict(d),
            comms_logger=CommsLoggerConfig.from_dict(_sub(d, C.COMMS_LOGGER)),
            flops_profiler=FlopsProfilerConfig.from_dict(_sub(d, C.FLOPS_PROFILER)),
            checkpoint=CheckpointConfig.from_dict(_sub(d, C.CHECKPOINT)),
            telemetry=TelemetryConfig.from_dict(_sub(d, C.TELEMETRY)),
            sentinel=SentinelConfig.from_dict(_sub(d, "sentinel")),
            progressive_layer_drop=ProgressiveLayerDropConfig.from_dict(
                _sub(d, "progressive_layer_drop")),
            data_efficiency=DataEfficiencyConfig.from_config_dict(d),
            gradient_clipping=float(d.get(C.GRADIENT_CLIPPING,
                                          C.GRADIENT_CLIPPING_DEFAULT)),
            prescale_gradients=bool(d.get(C.PRESCALE_GRADIENTS, False)),
            gradient_predivide_factor=float(d.get(C.GRADIENT_PREDIVIDE_FACTOR, 1.0)),
            steps_per_print=int(d.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)),
            wall_clock_breakdown=bool(d.get(C.WALL_CLOCK_BREAKDOWN, False)),
            seed=int(d.get(C.SEED, C.SEED_DEFAULT)),
            dump_state=bool(d.get(C.DUMP_STATE, False)),
        )
        if dp_world_size is not None:
            cfg.resolve_batch_sizes(dp_world_size)
        return cfg

    # ---------------------------------------------------------- batch invariant
    def resolve_batch_sizes(self, dp_world_size: int) -> None:
        """Enforce/derive ``train_batch = micro_batch × grad_accum × dp_world``
        (reference: ``runtime/config.py`` ``_set_batch_related_parameters``)."""
        d = self.raw
        tb = d.get(C.TRAIN_BATCH_SIZE)
        mb = d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        gas = d.get(C.GRADIENT_ACCUMULATION_STEPS)
        tb = None if tb == AUTO else tb
        mb = None if mb == AUTO else mb
        gas = None if gas == AUTO else gas

        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"batch invariant violated: train_batch_size={tb} != "
                    f"micro({mb}) × grad_accum({gas}) × dp_world({dp_world_size})")
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size={tb} not divisible by micro({mb}) × "
                    f"dp_world({dp_world_size})")
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size={tb} not divisible by grad_accum({gas}) × "
                    f"dp_world({dp_world_size})")
            mb = tb // (gas * dp_world_size)
        elif mb is not None:
            gas = gas or 1
            tb = mb * gas * dp_world_size
        elif tb is not None:
            mb = max(1, tb // dp_world_size)
            gas = tb // (mb * dp_world_size)
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"train_batch_size={tb} not divisible by dp_world({dp_world_size})")
        else:
            raise ValueError(
                "at least one of train_batch_size / train_micro_batch_size_per_gpu "
                "must be configured")
        self.train_batch_size = int(tb)
        self.train_micro_batch_size_per_gpu = int(mb)
        self.gradient_accumulation_steps = int(gas)

    # ------------------------------------------------------------------ helpers
    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def to_dict(self) -> Dict[str, Any]:
        out = dict(self.raw)
        out[C.TRAIN_BATCH_SIZE] = self.train_batch_size
        out[C.TRAIN_MICRO_BATCH_SIZE_PER_GPU] = self.train_micro_batch_size_per_gpu
        out[C.GRADIENT_ACCUMULATION_STEPS] = self.gradient_accumulation_steps
        return out
