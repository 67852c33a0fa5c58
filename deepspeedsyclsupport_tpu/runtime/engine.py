"""Training engine.

TPU-native analog of ``DeepSpeedEngine`` (``deepspeed/runtime/engine.py:179``, 3600 LoC)
and ``deepspeed.initialize`` (``deepspeed/__init__.py:64``): one config-driven object
wrapping a model with composed parallelism, precision policy, optimizer, LR schedule,
checkpointing, monitoring, and throughput accounting.

Structural shift from the reference (why this file is ~10× smaller):

* ``forward/backward/step`` there are eager passes threaded through hooks, buckets,
  and streams. Here the whole micro-step — forward, backward, grad accumulation,
  reduction, clip, optimizer, loss-scale bookkeeping — is ONE jitted SPMD program
  (``_build_train_batch_fn``), with gradient accumulation as ``lax.scan`` so it
  compiles once regardless of accumulation depth.
* ZeRO stages are placement policy (``runtime/zero.py``), not optimizer subclasses:
  the same train step serves stages 0-3; XLA inserts the all-gather/reduce-scatter
  traffic the reference implements by hand (``stage_1_and_2.py:1004``, ``stage3.py``).
* DP gradient averaging (reference ``allreduce_gradients`` ``engine.py:1903``) falls
  out of computing the *global* mean loss over a batch sharded on (data, fsdp).

The eager ``forward()/backward()/step()`` triple is still provided for loop parity
with reference user code, implemented over the same jitted kernels.
"""
import glob as glob_mod
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import zero as zero_lib
from .config import DSTpuConfig
from .dataloader import DSTpuDataLoader
from .loss_scaler import (LossScaleState, grads_finite, init_loss_scale, scale_loss,
                          unscale_grads, update_loss_scale)
from .lr_schedules import build_schedule
from .optimizers import build_optimizer, current_lr
from .sentinel import SENTINEL_GATE_KEY
from ..accelerator import get_accelerator
from ..checkpoint.engine import LATEST_FILE
from ..comm.comms_logging import comms_logger
from ..comm.topology import MeshTopology, build_topology
from ..utils.fault_injection import get_fault_injector
from ..monitor import MonitorMaster
from ..monitor.telemetry import setup_decision, setup_span
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                           STEP_GLOBAL_TIMER, SynchronizedWallClockTimer,
                           ThroughputTimer)


class _InitTuple(NamedTuple):
    """Return shape of :func:`initialize` for reference-style unpacking
    ``engine, optimizer, dataloader, lr_scheduler = initialize(...)``."""
    engine: "Engine"
    optimizer: Any
    training_dataloader: Any
    lr_scheduler: Any


def initialize(model: Any = None,
               loss_fn: Optional[Callable] = None,
               params: Any = None,
               config: Any = None,
               topology: Optional[MeshTopology] = None,
               training_data: Any = None,
               lr_schedule: Optional[Callable] = None,
               sharding_rules: Optional[Callable] = None,
               mpu: Any = None,
               dist_init_required: Optional[bool] = None,
               collate_fn: Optional[Callable] = None,
               config_params: Any = None) -> _InitTuple:
    """Build an :class:`Engine` (reference: ``deepspeed.initialize``,
    ``deepspeed/__init__.py:64``; arg names kept where meaningful).

    ``model``: anything exposing ``loss(params, batch, rng) -> loss | (loss, aux)``
    (our ``models/`` follow this protocol) — or pass ``loss_fn`` directly.
    ``params``: the initial parameter pytree (host arrays fine; engine places them).
    Left out, the engine initialises ``model.init_params`` under ``jit`` straight
    into its shards — the fp32 tree never lands whole on the default device.
    """
    from ..comm import init_distributed

    config = config if config is not None else config_params
    if config is None:
        raise ValueError("config (dict or json path) is required")
    init_distributed(dist_init_required=dist_init_required)

    if loss_fn is None:
        if model is None or not hasattr(model, "loss"):
            raise ValueError("provide loss_fn, or a model with a .loss method")
        loss_fn = model.loss
    if params is None:
        if model is not None and hasattr(model, "init_params"):
            params = model.init_params  # materialised sharded by the engine
        else:
            raise ValueError("provide params, or a model with init_params()")
    if sharding_rules is None and model is not None:
        sharding_rules = getattr(model, "sharding_rules", None)

    engine = Engine(loss_fn=loss_fn, params=params, config=config,
                    topology=topology, lr_schedule=lr_schedule,
                    sharding_rules=sharding_rules, module=model)
    dataloader = None
    if training_data is not None:
        dataloader = engine.register_dataloader(
            DSTpuDataLoader(training_data, engine.topology,
                            batch_fn=collate_fn))
    return _InitTuple(engine, engine.optimizer, dataloader, engine.lr_schedule)


class Engine:
    @setup_span("engine", side="train")
    def __init__(self, loss_fn: Callable, params: Any, config: Any,
                 topology: Optional[MeshTopology] = None,
                 lr_schedule: Optional[Callable] = None,
                 sharding_rules: Optional[Callable] = None,
                 module: Any = None):
        self.module = module
        self.loss_fn_raw = loss_fn
        import inspect

        try:
            self._loss_accepts_train = "train" in inspect.signature(
                loss_fn).parameters
        except (TypeError, ValueError):
            self._loss_accepts_train = False
        self.config = DSTpuConfig.from_config(config)

        # ---------------------------------------------------------- topology
        p = self.config.parallelism
        self.topology = topology or build_topology(dp=p.dp, fsdp=p.fsdp, tp=p.tp,
                                                   pp=p.pp, ep=p.ep, sp=p.sp)
        self.dp_world_size = self.topology.get_data_parallel_world_size()
        self.config.resolve_batch_sizes(self.dp_world_size)
        # Model-config overrides (pipe trunk, remat, random-LTD) are
        # COLLECTED here and applied to a per-engine private copy at the end
        # of __init__ — the engine never mutates a shared model's config in
        # place, so two engines on one model each trace their own
        # configuration (reference: PipelineEngine owns its stage count;
        # micro_batches is the pipeline.micro_batches knob).
        mcfg = getattr(self.module, "config", None)
        mcfg_overrides: Dict[str, Any] = {}
        if hasattr(mcfg, "pipe_stages"):
            # make the pipelined trunk an explicit model-config property
            mcfg_overrides["pipe_stages"] = self.topology.axis_sizes["pipe"]
            if p.pp_microbatches:
                mcfg_overrides["pipe_microbatches"] = p.pp_microbatches

        comms_logger.configure(enabled=self.config.comms_logger.enabled,
                               verbose=self.config.comms_logger.verbose)

        from ..checkpoint.ckpt_engine import build_checkpoint_engine

        self.checkpoint_engine = build_checkpoint_engine(
            self.config.checkpoint.engine)

        self.progressive_layer_drop = None
        if self.config.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop

            if self.config.data_efficiency.random_ltd is not None:
                raise ValueError(
                    "progressive_layer_drop and random_ltd cannot be "
                    "combined (both restructure the layer stack)")
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self.config.progressive_layer_drop.theta,
                gamma=self.config.progressive_layer_drop.gamma)

        # ---------------------------------------------------------- precision
        self.compute_dtype = self.config.compute_dtype
        fp16 = self.config.fp16
        self.fp16_enabled = fp16.enabled
        self.scaler_state = init_loss_scale(
            fp16.initial_scale if fp16.enabled else 1.0,
            dynamic=fp16.enabled and fp16.dynamic,
            hysteresis=fp16.hysteresis)

        # ---------------------------------------------------------- zero++
        zc = self.config.zero
        self._zeropp_enabled = (zc.zero_quantized_weights
                                or zc.zero_quantized_gradients
                                or zc.zero_hpz_partition_size > 1)
        if self._zeropp_enabled:
            axes = self.topology.axis_sizes
            n = axes["fsdp"]
            # TP composes: the explicit step is partially manual over
            # {data, fsdp} and leaves the model axis to XLA's partitioner
            # (reference runs hpZ/qwZ with Megatron TP —
            # ``partition_parameters.py:1551``, ``engine.py:849-858``)
            bad = [a for a in ("pipe", "seq", "expert") if axes[a] > 1]
            if zc.stage != 3 or n <= 1 or bad:
                raise ValueError(
                    f"ZeRO++ flags need stage 3 on a data/fsdp[/model] mesh "
                    f"with fsdp>1 (stage={zc.stage}, fsdp={n}, "
                    f"unsupported axes in use: {bad})")
            h = zc.zero_hpz_partition_size
            if h > 1 and n % h:
                raise ValueError(
                    f"zero_hpz_partition_size {h} must divide fsdp {n}")
            # offload composes: the explicit step's grads-only variant
            # feeds the host-resident master update (_build_grads_batch_fn)

        # ---------------------------------------------------------- optimizer
        sched_cfg = self.config.scheduler
        self.lr_schedule = lr_schedule or build_schedule(
            sched_cfg.type, sched_cfg.params, self.config.optimizer.lr)
        tx = build_optimizer(self.config.optimizer.type, self.config.optimizer.params,
                             self.lr_schedule)
        if (self.config.gradient_clipping and self.config.gradient_clipping > 0
                and not self._zeropp_enabled):
            # zero++ clips manually inside shard_map: optax's global-norm
            # transform would compute a per-shard norm there
            tx = optax.chain(
                optax.clip_by_global_norm(self.config.gradient_clipping), tx)
        self.optimizer = tx

        # ---------------------------------------------------------- placement
        stage = self.config.zero.stage
        self.zero_stage = stage
        # ``initialize`` hands over the model's init FUNCTION when the caller
        # gave no params: shardings come from its abstract shapes and the
        # values are generated under jit with those out_shardings below
        init_fn = params if callable(params) else None
        if init_fn is not None:
            params = jax.eval_shape(init_fn)
        self.param_shardings = zero_lib.tree_param_shardings(
            params, self.topology, stage, extra_rules=sharding_rules)
        # Stage >= 2: gradients (and the fp32 grad accumulator the scan
        # carries) live fsdp-sharded — the reference's IPG reduce-scatter
        # bucketing (``stage_1_and_2.py:894,1004``). The layout is exactly
        # the stage-3 param layout (TP dims composed, largest free dim over
        # fsdp). Computed before offload init: the multi-host offload path
        # reuses it as its shard layout.
        self.grad_shardings = None
        if stage >= 2 and self.topology.axis_sizes["fsdp"] > 1:
            self.grad_shardings = zero_lib.tree_param_shardings(
                params, self.topology, 3, extra_rules=sharding_rules)

        # -------------------------------------------------------- offload
        # ZeRO-Offload / ZeRO-Infinity (reference: cpu_adam host step
        # ``csrc/adam/cpu_adam.cpp``, stage3 optimizer-state swap
        # ``stage3.py:1816``, NVMe prefetch
        # ``partitioned_param_coordinator.py:503``). When enabled, the
        # device holds only compute-dtype working params; fp32 master
        # params + optimizer moments live on the host CPU backend, where the
        # update step runs as a second jitted program; 'nvme' additionally
        # round-trips the moments through the async swapper between steps.
        off_opt = self.config.zero.offload_optimizer
        off_par = self.config.zero.offload_param
        self.offload_device = None
        self._mh_offload = None     # multi-controller per-host shard swapping
        self._mh_push_fn = None
        self._multihost = False
        if off_opt.enabled or off_par.enabled:
            if jax.process_count() > 1:
                # per-host shard swapping (reference: CPUAdam partition
                # updates per rank + cross-rank grad-norm allreduce,
                # stage_1_and_2.py cpu_offload / stage3.py:1816): each
                # controller owns its fsdp shard's fp32 master + moments
                t = self.config.optimizer.type.lower().replace("_", "")
                if off_par.device == "nvme":
                    # multi-host NVMe swap covers OPTIMIZER state (the
                    # moments); parameter NVMe offload is single-controller
                    # only — accepting it here would silently leave params
                    # resident and OOM a ZeRO-Infinity-sized model
                    raise NotImplementedError(
                        "multi-host offload_param device='nvme' is not "
                        "wired; use offload_optimizer device='nvme' "
                        "(per-host moment swap) or offload_param='cpu'")
                if t not in ("adam", "adamw", "fusedadam", "cpuadam"):
                    raise ValueError(
                        "multi-host offload implements CPU Adam/AdamW only "
                        "(the reference's CPUAdam is likewise the only "
                        "offload optimizer); got optimizer type "
                        f"{self.config.optimizer.type!r}")
                if stage < 2 or self.topology.axis_sizes["fsdp"] <= 1:
                    raise ValueError(
                        "multi-host offload needs zero stage >= 2 with "
                        "fsdp > 1 so gradients land host-disjoint")
                self._multihost = True
            self.offload_device = ("nvme" if "nvme" in (off_opt.device,
                                                        off_par.device)
                                   else "cpu")
        self._swapper = None
        with setup_span("state"):   # master weights, optimizer state
            if self.offload_device is not None:
                self._init_offload(
                    init_fn() if init_fn is not None else params,
                    tx, off_opt, off_par)
            else:
                self.master_params = None
                if init_fn is not None:
                    self.params = jax.jit(
                        init_fn, out_shardings=self.param_shardings)()
                else:
                    self.params = jax.tree_util.tree_map(
                        lambda x, s: jax.device_put(jnp.asarray(x), s),
                        params, self.param_shardings)
                opt_shapes = jax.eval_shape(tx.init, self.params)
                self.opt_shardings = zero_lib.tree_optimizer_shardings(
                    opt_shapes, self.params, self.param_shardings,
                    self.topology, stage)
                self.opt_state = jax.jit(
                    tx.init, out_shardings=self.opt_shardings)(self.params)
        log_dist(zero_lib.describe_memory_plan(self.params, self.topology,
                                               stage, self.offload_device))

        # ---------------------------------------------------------- step fns
        self._train_batch_fn = None  # built lazily (needs gas)
        self._first_step_pending = True  # train_batch's set-up span
        self._remat_logged = None  # the rung last logged (_note_remat_choice)
        self._remat_auto = False   # the section names no policy: by room
        self._grad_fn = None
        self._apply_fn = None
        self._eval_fn = None
        self._host_apply = None

        # ---------------------------------------------------------- bookkeeping
        self.global_steps = 0
        self.micro_steps = 0
        self._accum_grads = None
        self._accum_count = 0
        self._accum_losses = []
        self._pending_events = []  # buffered monitor samples (see _post_step)
        self._resilience = None  # ResilienceManager (enable_preemption_handling)
        self._resilience_reported = {}  # last counter values flushed to monitor
        self._last_batch = None
        self._rng = jax.random.PRNGKey(self.config.seed)
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.config.train_batch_size,
            steps_per_output=self.config.steps_per_print)
        self.monitor = MonitorMaster(self.config.monitor)
        # Structured observability spine (monitor/telemetry.py): flight
        # recorder ring + rank-local JSONL, goodput accounting, recompile
        # detection, HBM gauges, heartbeat. None when the telemetry section
        # is off and DSTPU_TELEMETRY doesn't force it — the per-step guards
        # below then cost one attribute check.
        from ..monitor.telemetry import build_telemetry

        self.telemetry = build_telemetry(self.config, self.monitor)
        if self.telemetry is not None:
            # barrier-anchored alignment point for cross-rank trace fusion
            # (monitor/pod.py): engine construction is collective under
            # multiple controllers, so every rank stamps the same physical
            # instant through its own wall clock — the pod aggregator's
            # clock-offset ground truth. Single-process: a local marker.
            self.telemetry.anchor("engine_init")
        # Collective hang watchdog (comm/watchdog.py): a deadline armed
        # around each step's collective dispatch; expiry = stack dump +
        # recorder flush + rc-218 exit, the comm-hang contract the elastic
        # agent restarts distinctly from crash and preemption.
        self._watchdog = None
        # pod identity (utils/podid.py): jax.process_index under real
        # multi-controller, the env-declared RANK for pods of independent
        # single-controller replicas — rank-targeted fault injection and
        # the watchdog's rank labeling both key on it
        from ..utils.podid import pod_rank

        self._fi_rank = pod_rank()
        tw = self.config.telemetry
        if self.telemetry is not None and tw.watchdog_enabled:
            from ..comm.watchdog import CollectiveWatchdog

            self._watchdog = CollectiveWatchdog(
                deadline_s=tw.watchdog_deadline_s,
                warmup_deadline_s=tw.watchdog_warmup_deadline_s,
                poll_s=tw.watchdog_poll_s,
                rank=self._fi_rank,
                telemetry=self.telemetry,
                stack_path=os.path.join(
                    tw.output_dir, f"stacks_rank{self._fi_rank}.txt"),
            ).start()
            # telemetry.close() owns shutdown of the poll thread (engines
            # have no teardown of their own)
            self.telemetry.watchdog = self._watchdog

        # -------------------------------------------- activation checkpointing
        # (reference runtime/activation_checkpointing/: config-driven
        # save/recompute; here the section turns on jax.checkpoint around
        # each model layer and selects the rematerialization policy)
        if "activation_checkpointing" in self.config.raw:
            ac = self.config.activation_checkpointing
            if mcfg is None or not hasattr(mcfg, "remat"):
                logger.warning(
                    "activation_checkpointing configured but the model does "
                    "not expose a remat flag; apply jax.checkpoint in your "
                    "model instead")
            elif ac.enabled:
                # section presence = on (ported reference configs carry
                # partition_activations=false and still expect remat)
                if ac.cpu_checkpointing:
                    # reference cpu_checkpointing: saved activations move to
                    # host instead of recomputing — the XLA host-offload
                    # remat policy
                    mcfg_overrides["remat"] = True
                    mcfg_overrides["remat_policy"] = "offload_dots_to_host"
                    log_dist("cpu_checkpointing: dot activations offload to "
                             "pinned host memory")
                elif ac.policy is not None:
                    mcfg_overrides["remat"] = True
                    mcfg_overrides["remat_policy"] = ac.policy
                    log_dist(f"activation checkpointing on "
                             f"(policy={ac.policy})")
                else:
                    # no policy: the backward keeps what this device has
                    # room for (models/remat.py picks the rung where the
                    # stack is traced and the batch's shape is known; the
                    # rung is logged there: _note_remat_choice)
                    free = self._device_free_bytes()
                    mcfg_overrides["remat"] = True
                    mcfg_overrides["remat_policy"] = "auto"
                    self._remat_auto = True
                    mcfg_overrides["remat_free_bytes"] = free
                    log_dist(
                        "activation checkpointing on (policy by room: "
                        + ("the device reports no memory limit, "
                           "nothing_saveable" if free is None else
                           f"{free / 2**30:.2f} GiB of the device left to "
                           f"saved activations and the step's working set")
                        + ")")
            else:
                # explicit "enabled": false turns remat OFF — the
                # autotuner's off-arm on a shared model object. It also wins
                # over a contradictory cpu_checkpointing=true in the same
                # section (the explicit off-switch is authoritative).
                mcfg_overrides["remat"] = False
                if ac.cpu_checkpointing:
                    logger.warning(
                        "cpu_checkpointing requested but activation_"
                        "checkpointing.enabled is false — the explicit "
                        "off-switch wins; activations are not offloaded")

        # ------------------------------------------------- data efficiency
        # (reference: deepspeed/runtime/data_pipeline/ — curriculum seqlen
        # schedule + random-LTD token-drop schedule, both config-driven)
        de = self.config.data_efficiency
        self.curriculum_scheduler = None
        self.random_ltd_scheduler = None
        self._rltd_value = None
        if de.curriculum is not None:
            from .data_pipeline import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(de.curriculum)
        if de.random_ltd is not None:
            from .data_pipeline import RandomLTDScheduler

            self.random_ltd_scheduler = RandomLTDScheduler(de.random_ltd)
            if mcfg is None:
                raise ValueError("random_ltd needs a framework model "
                                 "(models.CausalLM) to drive token dropping")
            if not getattr(mcfg, "scan_layers", False) or \
                    getattr(mcfg, "num_layers", 0) < 3:
                raise ValueError(
                    "random_ltd requires a scan_layers model with >= 3 "
                    "layers (first/last stay dense; the middle stack drops "
                    "tokens) — got scan_layers="
                    f"{getattr(mcfg, 'scan_layers', None)}, num_layers="
                    f"{getattr(mcfg, 'num_layers', None)}")
            mcfg_overrides["random_ltd"] = True

        # -------------------------------------------- per-engine model view
        # Apply the collected config overrides to a PRIVATE shallow clone of
        # the model, and rebind a model-bound loss_fn onto the clone. The
        # caller's model object is left untouched: engines sharing one model
        # can no longer silently retrace each other's trunk (the r3
        # "functions traced earlier keep the old trunk" hazard), and the
        # per-step random-LTD keep-count mutation lands on engine-owned
        # state only.
        if self.module is not None and mcfg is not None and mcfg_overrides:
            import copy

            view_cfg = copy.copy(mcfg)
            for name, value in mcfg_overrides.items():
                setattr(view_cfg, name, value)
            view = copy.copy(self.module)
            view.config = view_cfg
            if getattr(self.loss_fn_raw, "__self__", None) is self.module:
                self.loss_fn_raw = getattr(view, self.loss_fn_raw.__name__)
            else:
                # a closure/partial loss_fn capturing the ORIGINAL model
                # cannot be rebound: it will trace the caller's config and
                # silently miss these overrides
                logger.warning(
                    "model-config overrides %s apply to the engine's "
                    "private model view, but the provided loss_fn is not a "
                    "bound method of the model and may still read the "
                    "original config — pass the model (engine binds "
                    "model.loss itself) or read config from the engine's "
                    "module", sorted(mcfg_overrides))
            self.module = view
        # --------------------------------------------------- QAT (in-forward)
        # reference runtime/quantize.py Quantizer: progressive bit schedule
        # over weight groups; compute copies are STE-fake-quantized in the
        # forward while the fp32 master stays exact
        from ..compression.qat import parse_qat_config

        self.qat_scheduler = parse_qat_config(self.config.raw)
        self._qat_bits: Dict[int, int] = {}
        if self.qat_scheduler is not None:
            # sync NOW: eval_batch/forward before the first train_batch must
            # already see the step-0 precision
            self._qat_bits, _ = self.qat_scheduler.update(0)

        from ..profiling.flops_profiler import FlopsProfiler

        self.flops_profiler = FlopsProfiler(self)
        # XLA timeline capture (the reference's NVTX-range story,
        # ``utils/nvtx.py`` + wall_clock_breakdown, recast as jax.profiler
        # traces viewable in TensorBoard/Perfetto): config section
        # {"jax_profiler": {"enabled": true, "trace_dir": ..., "start_step":
        # N, "num_steps": M}} brackets M train steps with a device trace
        jp = dict(self.config.raw.get("jax_profiler", {}))
        tcfg = self.config.telemetry
        if tcfg.trace_start_step is not None and \
                (tcfg.enabled or self.telemetry is not None):
            # telemetry.trace is the newer spelling of the same window knobs
            jp = {"enabled": True, "start_step": tcfg.trace_start_step,
                  "num_steps": tcfg.trace_num_steps,
                  "trace_dir": tcfg.trace_dir or jp.get("trace_dir")}
        env_start = os.environ.get("DSTPU_TRACE_START_STEP")
        if env_start:
            # env-triggered trace window: profile a misbehaving production
            # run without touching its config. A malformed value must not
            # kill the run the operator is trying to observe.
            try:
                jp = {"enabled": True, "start_step": int(env_start),
                      "num_steps": int(os.environ.get(
                          "DSTPU_TRACE_NUM_STEPS", jp.get("num_steps", 3))),
                      "trace_dir": (os.environ.get("DSTPU_TRACE_DIR")
                                    or jp.get("trace_dir"))}
            except ValueError as e:
                logger.warning(
                    "ignoring malformed DSTPU_TRACE_START_STEP/"
                    "DSTPU_TRACE_NUM_STEPS (%s); no trace window armed", e)
        self._trace_cfg = jp if jp.get("enabled") else None
        self._tracing = False
        self._trace_origin = None  # "config" windows auto-stop; manual don't
        # MFU-ledger window (telemetry.mfu): one-shot capture of a clean
        # (non-compiling) step into its own profiler trace dir; the join
        # against the roofline partition happens in mfu_ledger()
        self._mfu_pending = bool(self.telemetry is not None
                                 and tcfg.mfu_enabled)
        self._mfu_window = None
        self._mfu_attempts = 0
        self._mfu_compile_base = 0
        self._mfu_trace_dir = os.path.join(
            tcfg.output_dir, f"mfu_trace_rank{self._fi_rank}")
        # ------------------------------------------------ training sentinel
        # numerical-fault watchdog (runtime/sentinel.py): in-graph health
        # scalars + host-side spike detection + the warn/skip/rollback/abort
        # ladder. The registered dataloader (register_dataloader) is what
        # rollback rewinds; None when the section is off.
        self._dataloader = None
        self._sentinel = None
        if self.config.sentinel.enabled:
            from .sentinel import TrainingSentinel

            self._sentinel = TrainingSentinel(self, self.config.sentinel,
                                              rank=self._fi_rank)
        self.losses = None

    # ================================================================ offload
    def _init_offload(self, params, tx, off_opt, off_par):
        """Host-resident fp32 master + moments; compute-dtype device params."""
        pipe_cfg = off_opt if off_opt.enabled else off_par
        t = self.config.optimizer.type.lower().replace("_", "")
        adam_like = t in ("adam", "adamw", "fusedadam", "cpuadam")
        if not self._multihost and pipe_cfg.pipeline and not adam_like:
            # the pipelined host engine is a CPU Adam (the reference's
            # CPUAdam is likewise the only offload optimizer); other optax
            # optimizers keep the legacy jitted host path below
            log_dist(f"offload pipeline needs an Adam-family optimizer "
                     f"(got {self.config.optimizer.type!r}); using the "
                     f"jitted host-apply path")
        if self._multihost or (pipe_cfg.pipeline and adam_like):
            # Bucketed D2H / host-Adam / H2D pipeline with the bounded
            # NVMe moment window (runtime/multihost_offload.py +
            # offload_pipeline.py). Topology-agnostic: with one controller
            # the grad-norm allreduce degenerates to identity and the same
            # engine serves single-host ZeRO-Offload.
            from .multihost_offload import MultiHostCPUAdam
            from .optimizers import _common

            opt_params = self.config.optimizer.params
            _, betas, eps, wd = _common(opt_params)
            # mirror build_optimizer: plain "adam" with adam_w_mode=False is
            # optax.adam — no weight decay at all
            if t == "adam" and not opt_params.get("adam_w_mode", True):
                wd = 0.0
            fp16 = self.config.fp16
            mh_swapper = None
            if self.offload_device == "nvme":
                # ZeRO-Infinity across controllers: each host swaps ITS
                # moment shards to its own NVMe path (reference: every
                # rank swaps its own partition, stage3.py:1816). Private
                # to the optimizer — the engine's single-controller
                # _swapper machinery keys on opt_state, which is None here
                from .swap_tensor import AsyncTensorSwapper

                nvme_path = (off_opt.nvme_path or off_par.nvme_path
                             or os.path.join(os.getcwd(),
                                             "dstpu_nvme_swap"))
                mh_swapper = AsyncTensorSwapper(os.path.join(
                    nvme_path, f"rank{jax.process_index()}"))
            self._mh_offload = MultiHostCPUAdam(
                params,
                # shard layout: the ZeRO-3 grad layout when fsdp shards
                # exist, else the working-param layout (single controller /
                # fsdp=1 — every shard is host-addressable either way)
                self.grad_shardings if self.grad_shardings is not None
                else self.param_shardings,
                betas=betas, eps=eps,
                weight_decay=wd,
                clip=self.config.gradient_clipping,
                lr_fn=lambda step: float(np.asarray(
                    self.lr_schedule(step)
                    if callable(self.lr_schedule) else self.lr_schedule)),
                fp16_cfg=fp16, fp16_enabled=self.fp16_enabled,
                swapper=mh_swapper,
                bucket_bytes=pipe_cfg.bucket_size,
                window_buckets=pipe_cfg.buffer_count,
                overlap=pipe_cfg.overlap,
                push_dtype=jnp.dtype(self.compute_dtype))
            # the host CPU Adam runs the loss-scale state machine on host
            # (host_update_loss_scale): keep the state numpy-resident so
            # its per-step scale read is a plain float, never a device sync
            from .loss_scaler import host_loss_scale_state

            self.scaler_state = host_loss_scale_state(self.scaler_state)
            self.master_params = None
            self.opt_state = None
            self.opt_shardings = None
            self.params = self._push_params_to_device(params)
            return
        cpu = jax.local_devices(backend="cpu")[0]
        self._cpu_device = cpu

        def to_master(x):
            # async transfer to the host device (no blocking device_get
            # round trip); the fp32 promotion then runs on the host backend
            x = jax.device_put(x, cpu)
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(jnp.float32)
            return x

        self.master_params = jax.tree_util.tree_map(to_master, params)
        self.params = self._push_params_to_device(params)
        # master is cpu-committed, so jit compiles this for the host backend
        self.opt_state = jax.jit(tx.init)(self.master_params)
        self.opt_shardings = jax.tree_util.tree_map(
            lambda _: cpu, self.opt_state)
        if self.offload_device == "nvme":
            from .swap_tensor import AsyncTensorSwapper

            nvme_path = (off_opt.nvme_path or off_par.nvme_path
                         or os.path.join(os.getcwd(), "dstpu_nvme_swap"))
            self._swapper = AsyncTensorSwapper(os.path.join(
                nvme_path, f"rank{jax.process_index()}"))
            self._swap_out_opt_state()
        log_dist(f"offload: master+optimizer on "
                 f"{'NVMe(' + self._swapper.swap_dir + ')' if self._swapper else 'host CPU'}, "
                 f"device params dtype={jnp.dtype(self.compute_dtype).name}")

    def _mh_push(self, master_tree):
        """Jitted cast+reshard: shard (ZeRO-3) layout fp32 master → working
        param layout in compute dtype; any cross-host gather rides the
        ICI/DCN interconnect on device, never the hosts."""
        if self._mh_push_fn is None:
            dtype = self.compute_dtype

            def push(t):
                return jax.tree_util.tree_map(
                    lambda x: x.astype(dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x, t)

            self._mh_push_fn = jax.jit(push,
                                       out_shardings=self.param_shardings)
        return self._mh_push_fn(master_tree)

    def _push_params_to_device(self, master_tree):
        """Compute-dtype device working copies from the fp32 host master.
        The cast runs where each leaf already lives (the host backend for
        cpu-committed masters, numpy for raw init trees) and the transfer
        is an async ``device_put`` — no blocking ``device_get`` round trip
        and no transient commit to the default device (this runs once per
        step on the offload path)."""
        dtype = self.compute_dtype

        def push(x, s):
            if jnp.issubdtype(jnp.result_type(x), jnp.floating):
                x = x.astype(dtype)
            return jax.device_put(x, s)

        return jax.tree_util.tree_map(push, master_tree, self.param_shardings)

    def _swap_out_opt_state(self):
        """Moments → NVMe; drop the host copies (keeps shapes/treedef only)."""
        from ..checkpoint.engine import _leaf_paths

        self._opt_treedef = jax.tree_util.tree_structure(self.opt_state)
        leaves = jax.tree_util.tree_leaves(self.opt_state)
        self._opt_example = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           np.asarray(x).dtype),
            self.opt_state)
        names = _leaf_paths(self._opt_example)
        self._opt_names = names
        for name, leaf in zip(names, leaves):
            self._swapper.swap_out("opt/" + name, leaf)
        self.opt_state = None  # host memory released; state lives on disk

    def _prefetch_opt_state(self):
        for name in self._opt_names:
            self._swapper.prefetch("opt/" + name)

    def _swap_in_opt_state(self):
        leaves = [jax.device_put(self._swapper.retrieve("opt/" + n),
                                 self._cpu_device)
                  for n in self._opt_names]
        self.opt_state = jax.tree_util.tree_unflatten(self._opt_treedef,
                                                      leaves)

    def _build_grads_batch_fn(self):
        """Device half of the offloaded step: scan microbatches → grads."""
        if self._zeropp_enabled:
            from .zeropp import build_zeropp_grads_fn

            return build_zeropp_grads_fn(self)
        gas = self.config.gradient_accumulation_steps

        def grads_fn(params, scaler, batch, rng):
            def micro(carry, mb):
                acc, i = carry
                loss, metrics, grads = self._micro_grads(
                    params, mb, jax.random.fold_in(rng, i), scaler)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return (acc, i + 1), (loss, metrics)

            if gas == 1:
                loss, metrics, grads = self._micro_grads(params, batch, rng,
                                                         scaler)
                return grads, loss[None], metrics
            if self.grad_shardings is not None:
                # same 1/N accumulator layout as the fused path — this is the
                # device memory offload exists to save
                zero_grads = jax.tree_util.tree_map(
                    lambda p, s: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), s),
                    params, self.grad_shardings)
            else:
                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, _), (losses, metrics) = jax.lax.scan(
                micro, (zero_grads, 0), batch)
            grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
            metrics = jax.tree_util.tree_map(lambda m: m.mean(axis=0), metrics)
            return grads, losses, metrics

        return jax.jit(grads_fn)

    def _build_host_apply_fn(self):
        """Host half (the cpu_adam analog): fp32 master update on the CPU
        backend, returns the new master tree + scalar step metrics."""

        def apply_fn(master, opt_state, scaler, grads):
            new_master, new_opt, new_scaler, finite, grad_norm, _ = \
                self._apply_grads(master, opt_state, scaler, grads)
            return new_master, new_opt, new_scaler, {
                "grad_norm": grad_norm, "finite": finite,
                "loss_scale": new_scaler.scale}

        # all inputs are cpu-committed → compiles for the host backend
        return jax.jit(apply_fn, donate_argnums=(0, 1))

    def _offload_train_batch(self, batch, rng):
        if self._train_batch_fn is None:
            self._train_batch_fn = self._build_grads_batch_fn()
        if self._swapper is not None:
            self._prefetch_opt_state()  # overlap disk read with device grads
        # scaler lives host-side between steps (the update runs there);
        # replicate it onto the mesh for the device half
        dev_scaler = jax.device_put(self.scaler_state,
                                    self.topology.replicated())
        grads, losses, metrics = self._train_batch_fn(
            self.params, dev_scaler, batch, rng)
        m2 = self._host_step(grads)
        out = dict(metrics)
        out.update({k: m2[k] for k in ("grad_norm", "finite", "loss_scale")})
        out["loss"] = losses.mean()
        return out

    def _host_step(self, grads):
        """Shared tail of an offloaded step: grads → host, (swap in,) fp32
        master update on CPU, (swap out,) push compute-dtype params back."""
        if self._mh_offload is not None:
            new_master, self.scaler_state, m2 = self._mh_offload.step(
                grads, self.scaler_state)
            self.params = self._mh_push(new_master)
            # per-step transfer/stall ledger for telemetry (picked up by
            # on_step_end → Offload/* events + the goodput offload_stall
            # bucket); stash-and-pop so an eval between steps can't
            # double-report it
            self._last_offload_stats = self._mh_offload.last_stats
            return m2
        if self._host_apply is None:
            self._host_apply = self._build_host_apply_fn()
        # async device->host transfers (XLA gathers shards in flight); the
        # old device_get round trip blocked the dispatch pipeline here every
        # step — the host apply below is the only consumer that must wait
        host_grads = jax.tree_util.tree_map(
            lambda g: jax.device_put(g, self._cpu_device), grads)
        if self._swapper is not None and self.opt_state is None:
            self._swap_in_opt_state()
        scaler = jax.device_put(self.scaler_state, self._cpu_device)
        self.master_params, self.opt_state, self.scaler_state, m2 = \
            self._host_apply(self.master_params, self.opt_state,
                             scaler, host_grads)
        if self._swapper is not None:
            self._swap_out_opt_state()
        self.params = self._push_params_to_device(self.master_params)
        return m2

    # ================================================================ loss core
    def _cast_params(self, params):
        dtype = self.compute_dtype
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params)

    def _loss_and_metrics(self, params, batch, rng, train=True):
        p = self._cast_params(params)
        if self.qat_scheduler is not None and self._qat_bits:
            # eval included: QAT's point is measuring at deployment
            # precision (reference quantize_weight_in_forward quantizes the
            # module forward unconditionally)
            from ..compression.qat import apply_qat

            p = apply_qat(p, self._qat_bits, self.qat_scheduler.groups,
                          self.qat_scheduler.symmetric)
        if self._loss_accepts_train:
            out = self.loss_fn_raw(p, batch, rng, train=train)
        else:
            # user loss fns without a train flag (no train-time stochastic
            # behavior to gate)
            out = self.loss_fn_raw(p, batch, rng)
        if isinstance(out, tuple):
            loss, metrics = out
            metrics = dict(metrics)
        else:
            loss, metrics = out, {}
        return loss.astype(jnp.float32), metrics

    def _micro_grads(self, params, batch, rng, scaler):
        """One microbatch: scaled loss → grads (master-weight pattern: params are
        fp32, cast to compute dtype inside, so grads come back fp32)."""

        def scaled_loss(p):
            loss, metrics = self._loss_and_metrics(p, batch, rng)
            return scale_loss(loss, scaler), (loss, metrics)

        (_, (loss, metrics)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        # fp32 grads regardless of param dtype (under offload the device
        # params are compute-dtype; the master update must not consume
        # precision-truncated grads)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32)
            if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
        if self.grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, self.grad_shardings)
        return loss, metrics, grads

    def _apply_grads(self, params, opt_state, scaler, grads, ok=None,
                     emit_health=False):
        """Unscale, overflow-check, update, conditional-skip (reference:
        ``FP16_Optimizer.step`` unscale/overflow path + ``_take_model_step``
        ``engine.py:2054``). Traced under the ``optimizer`` MFU region
        (``monitor/mfu.py``) so the step-time ledger can price the update
        phase separately from forward/backward.

        ``ok`` (optional traced bool) is the sentinel's in-graph health
        verdict: when given, the update is additionally gated on it — same
        discard semantics as an fp16 overflow, but WITHOUT touching the
        loss-scale state machine (a spiked-but-finite step is not an
        overflow). ``emit_health=True`` adds the sentinel's device-side
        scalars (``runtime/sentinel.py health_metrics``) to the return."""
        from ..monitor.mfu import region_scope

        with region_scope("optimizer"):
            return self._apply_grads_impl(params, opt_state, scaler, grads,
                                          ok=ok, emit_health=emit_health)

    def _apply_grads_impl(self, params, opt_state, scaler, grads, ok=None,
                          emit_health=False):
        grads = unscale_grads(grads, scaler)
        # the sentinel needs the nonfinite check even in pure-fp32 runs
        # (where fp16's overflow machinery would skip it)
        finite = grads_finite(grads) \
            if (self.fp16_enabled or ok is not None) else jnp.asarray(True)
        grad_norm = optax.global_norm(grads)
        clip = self.config.gradient_clipping
        if self._zeropp_enabled and clip and clip > 0:
            # zero++ removes optax's global-norm transform from the chain
            # (it would mis-compute inside shard_map); on this pjit/eager
            # path clip manually so the configured clipping still applies
            scale_f = jnp.minimum(1.0, clip / jnp.maximum(grad_norm, 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * scale_f, grads)

        health = {}
        if emit_health:
            # post-unscale: region norms must not wander with the dynamic
            # loss scale or the host z-score history is meaningless
            from .sentinel import health_metrics

            health = health_metrics(grads)
        gate = finite if ok is None else (finite & ok)
        new_params, new_opt, new_scaler = self._finish_update(
            params, opt_state, scaler, grads, finite, gate=gate)
        return new_params, new_opt, new_scaler, finite, grad_norm, health

    def _finish_update(self, params, opt_state, scaler, grads, finite,
                       gate=None):
        """Shared post-norm tail: optimizer update, overflow-skip revert,
        loss-scale bookkeeping. Used by the pjit/eager paths and the ZeRO++
        shard_map body — fp16 skip semantics live in exactly one place.

        ``gate`` (default: ``finite``) decides whether the update is
        *applied*; ``finite`` alone keeps driving the loss-scale state
        machine — a sentinel-gated skip must not burn hysteresis or reset
        the scale-growth window."""
        if gate is None:
            gate = finite
        updates, new_opt = self.optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)

        def pick(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(gate, n, o) if hasattr(n, "dtype") else n,
                new, old)

        new_params = pick(new_params, params)
        new_opt = pick(new_opt, opt_state)
        fp16 = self.config.fp16
        new_scaler = update_loss_scale(
            scaler, finite, dynamic=self.fp16_enabled and fp16.dynamic,
            scale_window=fp16.loss_scale_window, min_scale=fp16.min_loss_scale,
            hysteresis=fp16.hysteresis)
        return new_params, new_opt, new_scaler

    # ================================================================ remat
    def _device_free_bytes(self) -> Optional[int]:
        """What one device has left for a step's activations: its
        ``bytes_limit`` less the margin of ``models/remat.py``, less the
        engine's resident state (parameters, optimizer state where it lives
        on the device, and the float32 gradients a step holds before the
        update). None where the device reports no limit (the CPU)."""
        from ..models.remat import MARGIN

        device = self.topology.mesh.devices.flat[0]
        limit = get_accelerator().total_memory(device)
        if not limit:
            return None

        leaves = jax.tree_util.tree_leaves

        def held(arrays, shardings=None, itemsize=None):
            """Bytes of ``arrays`` on one device, as their own or the given
            shardings split them."""
            arrays = leaves(arrays)
            shardings = leaves(shardings) or [x.sharding for x in arrays]
            return sum(math.prod(s.shard_shape(x.shape))
                       * (itemsize or x.dtype.itemsize)
                       for x, s in zip(arrays, shardings))

        resident = held(self.params) \
            + held(self.params, self.grad_shardings, 4)
        if self.offload_device is None:
            resident += held(self.opt_state)
        return int(limit * (1 - MARGIN)) - resident

    @property
    def remat_choice(self) -> Optional[Dict[str, Any]]:
        """What the last traced step's checkpointed layers keep, where the
        model took a rung of ``models/remat.py``: ``{"rung", "saved_bytes",
        "auto"}`` (``auto``: by room, no ``policy`` in the section); None
        before the first trace and for a model or policy that takes none."""
        choice = getattr(self.module, "remat_choice", None)
        return choice and {**choice, "auto": self._remat_auto}

    def _note_remat_choice(self) -> None:
        """Log the rung a freshly traced step took (once a rung)."""
        choice = self.remat_choice
        if choice is not None and choice != self._remat_logged:
            self._remat_logged = dict(choice)
            setup_decision("remat", **choice)
            log_dist(f"activation checkpointing: rung {choice['rung']}, "
                     f"{choice['saved_bytes'] / 2**30:.3f} GiB saved for the "
                     f"backward pass on each device")

    def _remat_step_down(self, err: Exception) -> bool:
        """The compiled step did not fit (the compiler's temporaries are not
        in the rung's arithmetic): take the next leaner rung and build the
        step again. False where that is not the fault or nothing is left to
        give up: an explicit policy, the leanest rung, a step that had
        already consumed its donated state."""
        from ..models.remat import RUNGS

        choice = self.remat_choice
        if "RESOURCE_EXHAUSTED" not in str(err) or not self._remat_auto \
                or choice is None or choice["rung"] == RUNGS[-1] \
                or any(x.is_deleted()
                       for x in jax.tree_util.tree_leaves(self.params)):
            return False
        leaner = RUNGS[RUNGS.index(choice["rung"]) + 1]
        logger.warning(
            "the train step does not fit the device with rung %s (%.3f GiB "
            "saved): falling back to %s and compiling again",
            choice["rung"], choice["saved_bytes"] / 2**30, leaner)
        # the step's second build stands in the set-up ledger behind this;
        # what the leaner rung saves is the next remat decision's to say
        setup_decision("remat", rung=leaner, saved_bytes=None,
                       auto=True, stepped_down_from=choice["rung"],
                       saved_bytes_before=choice["saved_bytes"])
        self.module.config.remat_policy = leaner
        self.module.remat_choice = None
        self._train_batch_fn = self._build_train_batch_fn()
        return True

    # ================================================================ fused path
    def _build_train_batch_fn(self):
        if self._zeropp_enabled:
            from .zeropp import build_zeropp_train_fn

            self._train_batch_raw = None  # explicit shard_map path
            if self.config.flops_profiler.enabled:
                logger.warning(
                    "flops_profiler is not available on the ZeRO++ explicit "
                    "shard_map path; profiling is disabled for this run")
            return build_zeropp_train_fn(self)
        gas = self.config.gradient_accumulation_steps

        def train_batch_fn(params, opt_state, scaler, batch, rng):
            # sentinel gate rider (runtime/sentinel.py): popped BEFORE the
            # accumulation scan (it is per-step, not per-microbatch — same
            # reason pld_theta is broadcast but this is not sliced)
            gate = None
            if isinstance(batch, dict) and SENTINEL_GATE_KEY in batch:
                batch = dict(batch)
                gate = batch.pop(SENTINEL_GATE_KEY)

            def micro(carry, mb):
                acc, i = carry
                loss, metrics, grads = self._micro_grads(
                    params, mb, jax.random.fold_in(rng, i), scaler)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return (acc, i + 1), (loss, metrics)

            if self.grad_shardings is not None:
                zero_grads = jax.tree_util.tree_map(
                    lambda p, s: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), s),
                    params, self.grad_shardings)
            else:
                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if gas == 1:
                loss, metrics, grads = self._micro_grads(params, batch, rng, scaler)
                losses = loss[None]
            else:
                (grads, _), (losses, metrics) = jax.lax.scan(
                    micro, (zero_grads, 0), batch)
                grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
                metrics = jax.tree_util.tree_map(lambda m: m.mean(axis=0), metrics)
            ok = None
            if gate is not None:
                # in-graph health verdict: discard the update when the mean
                # loss clears the sentinel's cap. NaN compares False, so a
                # nonfinite loss is gated even before the host has history.
                ok = losses.mean() <= gate[0]
                # transient post-rollback LR cut (gate[1] is 1.0 otherwise —
                # an exact float no-op)
                grads = jax.tree_util.tree_map(lambda g: g * gate[1], grads)
            new_params, new_opt, new_scaler, finite, grad_norm, health = \
                self._apply_grads(params, opt_state, scaler, grads, ok=ok,
                                  emit_health=gate is not None)
            out_metrics = {
                **metrics,
                **health,
                "loss": losses.mean(),
                "grad_norm": grad_norm,
                "finite": finite,
                "loss_scale": new_scaler.scale,
            }
            return new_params, new_opt, new_scaler, out_metrics

        self._train_batch_raw = train_batch_fn  # unjitted, for the profiler
        return jax.jit(train_batch_fn, donate_argnums=(0, 1, 2))

    def train_batch(self, batch) -> Dict[str, Any]:
        """Full optimizer step on one *global* batch (leading dim =
        ``train_batch_size``; with accumulation the engine reshapes to
        ``(gas, step_batch, ...)`` and scans). The analog of the reference loop
        forward→backward→step and of ``PipelineEngine.train_batch``
        (``pipe/engine.py:321``)."""
        if self._first_step_pending:
            # the engine's first step is set-up: trace, lowering, compile or
            # cache load and the dispatch of the first execution
            self._first_step_pending = False
            with setup_span("first_step"):
                return Engine.train_batch(self, batch)
        if self._sentinel is not None and self._sentinel.offer_batch():
            # journaled bad position being replayed (post-rollback or
            # post-restart): consume-and-discard BEFORE any dispatch. No
            # global_steps increment — the replayed trajectory keeps the
            # clean run's step numbering (and with it the per-step
            # fold_in(rng, global_steps) stream), which is what makes the
            # resumed losses float-identical to a run that never saw the
            # bad batch.
            return None
        if self.curriculum_scheduler is not None:
            # seqlen curriculum: clip the batch before compile — each
            # difficulty level is one compiled program (difficulty_step
            # bounds the number of levels)
            d = self.curriculum_scheduler.update_difficulty(self.global_steps)
            from .data_pipeline import truncate_to_difficulty

            batch = truncate_to_difficulty(batch, d)
        if self.random_ltd_scheduler is not None:
            v = self.random_ltd_scheduler.get_value(self.global_steps)
            if v != self._rltd_value:
                self._rltd_value = v
                self.module.config.random_ltd_current = v
                self._train_batch_fn = None  # retrace at the new keep count
        if self.qat_scheduler is not None:
            bits, changed = self.qat_scheduler.update(self.global_steps)
            if changed:
                self._qat_bits = bits
                # every cached program bakes the bits in: retrace them all
                self._train_batch_fn = None
                self._eval_fn = None
                self._grad_fn = None
        if self._train_batch_fn is None and self.offload_device is None:
            self._train_batch_fn = self._build_train_batch_fn()
        gas = self.config.gradient_accumulation_steps
        if gas > 1:
            batch = jax.tree_util.tree_map(
                lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]), batch)
        if self.progressive_layer_drop is not None:
            # θ rides the batch as a traced scalar — it decays every step and
            # must never trigger a retrace (reference: PLD state dict merged
            # into the module kwargs, progressive_layer_drop.py get_state).
            # Injected AFTER the accumulation reshape: under gas>1 the scan
            # slices a (gas,) vector down to the per-microbatch scalar
            theta = self.progressive_layer_drop.update_state(self.global_steps)
            t = jnp.asarray(theta, jnp.float32)
            batch = {**batch,
                     "pld_theta": jnp.broadcast_to(t, (gas,)) if gas > 1
                     else t}
        if self._sentinel is not None and self.offload_device is None and \
                not self._zeropp_enabled and isinstance(batch, dict):
            # health-gate rider ([loss_cap, grad_scale], popped inside
            # train_batch_fn before the scan). Injected every armed step:
            # its PRESENCE changes the treedef (one retrace when arming),
            # its VALUES are data and retrace nothing.
            batch = {**batch,
                     SENTINEL_GATE_KEY: self._sentinel.gate_array()}
        if self._trace_cfg is not None and not self._tracing and \
                self.global_steps == int(self._trace_cfg.get("start_step", 1)):
            self.start_profile()
            self._trace_origin = "config"
        self.tput_timer.start()
        rng = jax.random.fold_in(self._rng, self.global_steps)
        fi = get_fault_injector()
        # this call executes what will be recorded as step global_steps+1
        # (the counter increments after dispatch): arm/hang stamps use that
        # number so they join exactly against the step span in the stream
        stepno = self.global_steps + 1
        if fi.armed:
            # rank-targeted comm-layer fault (utils/fault_injection.py): a
            # hang HERE is "this rank never arrives at the collective" —
            # siblings spin inside the all-reduce and only their watchdogs
            # (or the agent's teardown) end the pod
            fi.maybe_hang_step(self._fi_rank, stepno)
            # numerical fault (nan_step/loss_spike/bad_batch): poison the
            # data, not the riders — the sentinel must detect through its
            # own gate, and pld/gate scalars are engine state
            batch = fi.corrupt_batch(self._fi_rank, stepno, batch,
                                     skip_keys=("pld_theta",
                                                SENTINEL_GATE_KEY))
        if self._watchdog is not None:
            # pre-dispatch deadline stamp: the collective phase is armed
            # until the step's results are back (disarm in the finally
            # below — an exception mid-dispatch must not leave the deadline
            # live, or the watchdog would rc-218 the process ~deadline_s
            # later while the caller handles an ordinary error)
            self._watchdog.arm(stepno)
        # one-shot MFU trace window (telemetry.mfu): bracket EXACTLY this
        # step with a jax.profiler trace. Offload splits the step across
        # two programs and manual trace windows would nest — both skip.
        mfu_capture = False
        if self._mfu_pending and not self._tracing and \
                self.offload_device is None and \
                stepno >= self.config.telemetry.mfu_step:
            from ..monitor.telemetry import compile_stats

            self._mfu_compile_base = compile_stats()[0]
            try:
                # drain the async backlog FIRST: params are step N-1's
                # output, so waiting on one leaf retires every prior
                # step's device work — otherwise the window records their
                # tail and bills it into this step's regions
                jax.block_until_ready(  # dslint: allow(host-sync-in-step-path)
                    jax.tree_util.tree_leaves(self.params)[:1])
                jax.profiler.start_trace(self._mfu_trace_dir)
                mfu_capture = True
            except Exception as e:  # a broken profiler must not kill training
                logger.warning("mfu trace window failed to start: %s", e)
                self._mfu_pending = False
        t_step = time.perf_counter()
        try:
            if fi.armed:
                # phase="in": the rank ARRIVED (armed) and then wedged
                # inside its collective window — this rank's own watchdog
                # fires, exercising the self-abort half of the rc-218
                # contract
                fi.maybe_hang_step(self._fi_rank, stepno, phase="in")
            if self.offload_device is not None:
                metrics = self._offload_train_batch(batch, rng)
            else:
                # abstract avals (+ shardings) of EXACTLY this step's args —
                # curriculum truncation, gas reshape and pld_theta included —
                # so the compiled program can be re-lowered (a compile-cache
                # hit) for HLO-level comms accounting and graph_report
                # without holding the donated arrays. Avals only carry
                # shape/dtype/sharding, and params/opt/scaler keep theirs
                # across steps, so the full O(param-leaves) tree_map reruns
                # only when the batch/rng metadata actually changes
                # (curriculum truncation step, gas reshape) — not every step.
                key = (jax.tree_util.tree_structure((batch, rng)), tuple(
                    (jnp.shape(x), jnp.result_type(x),
                     getattr(x, "sharding", None))
                    for x in jax.tree_util.tree_leaves((batch, rng))))
                if key != getattr(self, "_last_aval_key", None) or \
                        getattr(self, "_last_train_avals", None) is None:
                    from ..analysis.capture import abstract_step_args

                    self._last_train_avals = abstract_step_args(
                        (self.params, self.opt_state, self.scaler_state,
                         batch, rng))
                    self._last_aval_key = key
                while True:
                    try:
                        self.params, self.opt_state, self.scaler_state, \
                            metrics = self._train_batch_fn(
                                self.params, self.opt_state,
                                self.scaler_state, batch, rng)
                        break
                    except jax.errors.JaxRuntimeError as e:
                        if not self._remat_step_down(e):
                            raise
                self._note_remat_choice()
            if comms_logger.enabled:
                # opt-in (comms_logger.enabled): straggler wall-clock must
                # be device-accurate, so this config knowingly trades the
                # overlap
                jax.block_until_ready(metrics["loss"])  # dslint: allow(host-sync-in-step-path)
                comms_logger.record_wall("train_batch",
                                         time.perf_counter() - t_step)
            elif self.telemetry is not None and self.telemetry.cfg.sync_timing:
                # telemetry.sync_timing: device-accurate step spans — trades
                # the dispatch/compute overlap for timing fidelity (see
                # on_step_end)
                jax.block_until_ready(metrics["loss"])  # dslint: allow(host-sync-in-step-path)
            # NOTE (watchdog + async dispatch): with neither sync knob on,
            # the jitted call can return before the device work runs, so a
            # purely device-side hang is caught when XLA's bounded
            # in-flight queue blocks a LATER dispatch — still inside an
            # armed window, so rc-218 fires, but attribution may name a
            # step a few later than the wedged one. telemetry.sync_timing
            # opts into device-accurate (exact-step) windows at the
            # documented cost of the dispatch/compute overlap.
        finally:
            if self._watchdog is not None:
                # post-dispatch: the step span recorded in on_step_end
                # below is the durable post record the pod report joins
                self._watchdog.disarm(stepno)
            if mfu_capture and sys.exc_info()[0] is not None:
                # exception mid-dispatch: close the profiler session so a
                # caller that survives the error can still trace later
                try:
                    jax.profiler.stop_trace()
                except Exception:  # pragma: no cover - defensive
                    pass
                mfu_capture = False
        step_dur = time.perf_counter() - t_step
        if mfu_capture:
            # sync + close the window; the synced wall is the ledger's
            # clean-step time (one deliberately-blocking step)
            step_dur = self._finish_mfu_window(stepno, t_step, metrics)
        self.global_steps += 1
        self.micro_steps += gas
        if self.telemetry is not None:
            # step span + recompile attribution + goodput + heartbeat +
            # periodic HBM gauges — a few host dict appends (<5% guarded by
            # tests/unit/test_telemetry.py::test_telemetry_overhead)
            self.telemetry.on_step_end(self.global_steps, step_dur,
                                       batch=batch,
                                       offload=self._pop_offload_stats())
        if self._tracing and self._trace_origin == "config":
            start = int(self._trace_cfg.get("start_step", 1))
            n = int(self._trace_cfg.get("num_steps", 3))
            # close INSIDE the last in-window call — a loop that ends with
            # the window would otherwise exit with the trace open and no
            # artifacts written
            if self.global_steps >= start + n:
                self.stop_profile()
        if (self.config.flops_profiler.enabled and self.offload_device is None
                and getattr(self, "_train_batch_raw", None) is not None):
            # post-donation the old state is gone; new state has identical
            # shapes, which is all static FLOP analysis needs
            self.flops_profiler.maybe_profile(
                self._train_batch_raw,
                (self.params, self.opt_state, self.scaler_state, batch, rng))
        self._post_step(metrics)
        if fi.armed:
            rc = fi.should_kill(self._fi_rank, self.global_steps)
            if rc is not None:
                # a hard crash, not a preemption: no emergency save, no
                # cleanup — the elastic agent's prompt-teardown path is
                # what this fault exists to exercise
                logger.error("fault injection: rank %d dying with rc=%d "
                             "after step %d", self._fi_rank, rc,
                             self.global_steps)
                if self.telemetry is not None:
                    try:
                        self.telemetry.dump("injected_kill")
                    except Exception:
                        pass
                os._exit(rc)
        return metrics

    def start_profile(self, trace_dir: Optional[str] = None) -> None:
        """Begin an XLA device-timeline capture (jax.profiler trace —
        TensorBoard/Perfetto-viewable; the role NVTX ranges + nsys play for
        the reference). Also usable manually around any region."""
        if self._tracing:
            return
        trace_dir = trace_dir or (self._trace_cfg or {}).get(
            "trace_dir") or os.path.join(os.getcwd(), "dstpu_traces")
        jax.profiler.start_trace(trace_dir)
        self._tracing = True
        self._trace_origin = "manual"  # train_batch overrides for windows
        import atexit

        atexit.register(self.stop_profile)  # never exit with an open trace
        log_dist(f"jax.profiler trace started -> {trace_dir}")

    def stop_profile(self) -> None:
        if not self._tracing:
            return
        jax.block_until_ready(jax.tree_util.tree_leaves(self.params)[:1])
        jax.profiler.stop_trace()
        self._tracing = False
        self._trace_origin = None
        log_dist("jax.profiler trace stopped")

    def xla_comms_summary(self, log: bool = True,
                          show_straggler: bool = False) -> Dict[str, Dict]:
        """Post-compile accounting of the collectives XLA's partitioner
        inserted into the fused train step — the traffic the façade logger
        can never see (VERDICT r3 #6; reference ``log_summary`` via
        ``comm/comm.py:422``). Re-lowers the train program at the last
        step's avals (a compile-cache hit), parses the optimized HLO, and
        merges per-opcode byte totals into ``comms_logger``."""
        if not comms_logger.enabled or \
                getattr(self, "_last_train_avals", None) is None:
            # avals are captured on every step now, but the summary merges
            # into comms_logger state — without the logger it has nowhere
            # to land (use graph_report() for logger-free analysis)
            raise RuntimeError(
                "run train_batch() with comms_logger enabled first "
                "(config comms_logger.enabled: true)")
        from ..comm.hlo_comms import summarize_compiled

        summary = summarize_compiled(self.compiled_train_step())
        comms_logger.record_hlo(summary, tag="train_step")
        if log:
            comms_logger.log_summary(show_straggler=show_straggler)
        return summary

    def emit_comm_census(self) -> Dict[str, Any]:
        """Classify the compiled train step's collectives into traffic
        classes (``analysis/collectives.py``) and persist the class summary
        as a ``comm/census`` flight-recorder event — the static half of the
        pod report's bytes/time/bandwidth join (``monitor/pod.py``). Also
        records the raw per-opcode mix into ``comms_logger`` (when enabled)
        so a ``comm/snapshot`` lands beside it on the next dump, giving the
        offline join its measured cross-check. Returns the payload."""
        report = self.graph_report(analyzers=("collectives",))
        payload: Dict[str, Any] = {
            "classes": report["collectives"].classes.summary(),
            "group_size": report["collectives"].expectation.group_size,
            "n_devices": int(np.prod(list(self.topology.axis_sizes.values()))),
            "zero_stage": self.zero_stage,
        }
        if comms_logger.enabled:
            # merge the measured op mix from the same compiled program into
            # comms_logger (xla:: keys) so the next dump's comm/snapshot
            # carries it
            self.xla_comms_summary(log=False)
        if self.telemetry is not None:
            self.telemetry.record_census(payload)
        return payload

    def compiled_train_step(self):
        """The fused train step EXACTLY as the last ``train_batch`` ran it
        (``jax.stages.Compiled``), re-lowered from the avals captured at its
        call site — ``.as_text()`` shows the kernels and collectives that
        ran, ``.memory_analysis()`` the bytes per device. The step is also
        handed to ``monitor/mfu.py`` under its program name
        (``train_batch_fn``), which reads every instruction's region and
        pass off its text for whoever asks (``mfu.published``): this
        engine's ``mfu_ledger()``, the benchmark's ``train_*_ms`` readers.
        With it goes what the step's checkpointed layers keep
        (:attr:`remat_choice`; ``mfu.step_record``)."""
        avals = getattr(self, "_last_train_avals", None)
        if self._train_batch_fn is None or avals is None:
            raise RuntimeError("run train_batch() first")
        compiled = self._train_batch_fn.lower(*avals).compile()
        from ..monitor import mfu as mfu_mod

        mfu_mod.publish(self._train_batch_fn.__name__, compiled,
                        remat=self.remat_choice)
        return compiled

    GRAPH_ANALYZERS = ("collectives", "donation", "resharding", "dtype")

    def graph_report(self, gathers_per_param: Optional[int] = None,
                     analyzers: Tuple[str, ...] = GRAPH_ANALYZERS,
                     ) -> Dict[str, Any]:
        """Static analysis of the compiled train step (``analysis/``):
        collective census vs the analytic parallelism expectation, donation
        audit, activation dtype audit and resharding detection.

        Audits EXACTLY the program the last ``train_batch`` ran, from the
        avals captured at its call site (re-lowering is a compile-cache
        hit). ``analyzers`` selects a subset — the dtype audit re-traces
        the raw step with ``make_jaxpr``, which a caller that only wants
        one report (``benchmark/run.py`` asks for the census alone) should
        not pay for.

        ``gathers_per_param`` defaults from this engine's own remat config
        (2 when activation checkpointing is on — backward may legally
        re-gather each ZeRO-3 param — else 1); the analytic budget must
        not flag a correct remat graph. XLA often hoists the gather out
        of the remat region anyway, and ``exact=False`` treats the
        expectation as a ceiling, so 2 stays sound either way.
        """
        if gathers_per_param is None:
            ac = "activation_checkpointing" in self.config.raw and \
                self.config.activation_checkpointing.enabled
            gathers_per_param = 2 if ac else 1
        from ..analysis import (check_collectives, collective_census,
                                donation_audit, dtype_audit,
                                expected_train_collectives, resharding_audit)

        if self.offload_device is not None:
            raise RuntimeError(
                "graph_report audits the fused train step; the offload path "
                "splits the step into a grads fn + host apply — audit those "
                "directly with the analysis.* functions")
        compiled = self.compiled_train_step()
        avals = self._last_train_avals
        report: Dict[str, Any] = {}
        if "collectives" in analyzers or "resharding" in analyzers:
            report["census"] = collective_census(compiled)
        if "collectives" in analyzers:
            expectation = expected_train_collectives(
                avals[0], self.topology, self.zero_stage,
                param_shardings=self.param_shardings,
                gathers_per_param=gathers_per_param)
            report["collectives"] = check_collectives(
                report["census"], expectation, avals[0],
                self.param_shardings, exact=False)
        if "donation" in analyzers:
            report["donation"] = donation_audit(compiled, avals,
                                                donate_argnums=(0, 1, 2))
        if "resharding" in analyzers:
            report["resharding"] = resharding_audit(
                compiled, params=avals[0],
                param_shardings=self.param_shardings,
                census=report["census"])
        if "dtype" in analyzers:
            param_shapes = [tuple(np.shape(p))
                            for p in jax.tree_util.tree_leaves(avals[0])]
            report["dtype"] = dtype_audit(
                jax.make_jaxpr(self._train_batch_raw)(*avals)
                if getattr(self, "_train_batch_raw", None) is not None else
                jax.make_jaxpr(lambda *a: self._train_batch_fn(*a))(*avals),
                allowed_shapes=param_shapes)
        return report

    # ================================================================ mfu
    def _finish_mfu_window(self, stepno: int, t_step: float,
                           metrics: Dict[str, Any]) -> float:
        """Close the one-shot MFU trace window: block on the step's result
        (the window's step wall must be device-accurate — this is the one
        deliberately-synced step), stop the trace, and keep the window only
        if the step compiled nothing (a compile inside the window is not a
        clean step; re-arm for a later one, bounded). Returns the synced
        step duration so goodput accounts the real wall either way."""
        try:
            jax.block_until_ready(metrics["loss"])  # dslint: allow(host-sync-in-step-path)
        finally:
            dur = time.perf_counter() - t_step
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # pragma: no cover - defensive
                logger.warning("mfu trace window failed to stop: %s", e)
                self._mfu_pending = False
                return dur
        from ..monitor.telemetry import compile_stats

        self._mfu_attempts += 1
        if compile_stats()[0] - self._mfu_compile_base > 0:
            if self._mfu_attempts >= 5:
                self._mfu_pending = False
                logger.warning(
                    "mfu window: no clean (non-compiling) step within 5 "
                    "attempts — shape thrash? see Compile/* events; giving "
                    "up on the ledger capture")
            return dur
        self._mfu_pending = False
        self._mfu_window = {"step": stepno, "step_s": dur, "steps": 1,
                            "trace_dir": self._mfu_trace_dir}
        if self.telemetry is not None:
            self.telemetry.recorder.record(
                "event", "mfu/window", step=stepno,
                data={"step_s": dur, "steps": 1,
                      "trace_dir": self._mfu_trace_dir})
        return dur

    def mfu_ledger(self, spec: Any = None, persist: bool = True
                   ) -> Dict[str, Any]:
        """The step-time attribution ledger (docs/observability.md "MFU
        ledger"): joins (1) the roofline partition of the compiled step's
        jaxpr into named regions (``analysis/roofline.py`` — analytic
        FLOPs / HBM bytes / comm bytes per ``mfu.*`` scope, priced against
        the device peak-spec registry), (2) the measured per-op times of
        the captured clean-step trace window grouped by region via the
        named_scope metadata XLA stamped into the compiled HLO
        (``monitor/mfu.py``), and (3) the HLO collective census
        (partitioner-inserted traffic the jaxpr can't see). Emits the
        strict ``MFU/*`` event family, persists the offline artifacts
        (opmap/roofline/window/ledger JSON next to the trace, the
        ``tools/mfu_report.py`` contract) and returns the ledger dict.

        Requires a captured window (``telemetry.mfu``) and the fused train
        path — the ZeRO++ explicit step has no retraceable raw fn, and
        offload splits the step across two programs."""
        from ..analysis import collective_census, roofline
        from ..monitor import mfu as mfu_mod

        if self._mfu_window is None:
            raise RuntimeError(
                "no MFU trace window captured — enable telemetry.mfu "
                '({"telemetry": {"enabled": true, "mfu": {"enabled": '
                'true}}}) and run past telemetry.mfu.step clean steps')
        if self._train_batch_fn is None or \
                getattr(self, "_last_train_avals", None) is None or \
                getattr(self, "_train_batch_raw", None) is None:
            raise RuntimeError(
                "mfu_ledger audits the fused train step — run train_batch"
                "() first (ZeRO++ explicit-shard_map and offload split "
                "steps are not supported)")
        avals = self._last_train_avals
        compiled = self.compiled_train_step()
        opmap = mfu_mod.published(self._train_batch_fn.__name__)
        costs = roofline.region_costs(
            jax.make_jaxpr(self._train_batch_raw)(*avals))
        census_bytes = sum(e["bytes"] * e["executions"]
                           for e in collective_census(compiled))
        spec = spec or roofline.device_spec()
        table = roofline.roofline_table(costs, spec,
                                        census_bytes=census_bytes)
        w = self._mfu_window
        trace_path = mfu_mod.find_trace(w["trace_dir"])
        if trace_path is None:
            raise RuntimeError(f"no trace file under {w['trace_dir']} — "
                               f"profiler produced no artifacts")
        events, meta = mfu_mod.parse_trace(trace_path)
        measured = mfu_mod.measure_regions(events, opmap,
                                           steps=w.get("steps", 1))
        led = mfu_mod.ledger(table, measured, w["step_s"],
                             truncated_trace=meta["truncated"])
        led["window"] = {"step": w["step"], "trace_path": trace_path}
        led["remat"] = mfu_mod.step_record(
            self._train_batch_fn.__name__).get("remat")
        if persist:
            # the offline-report artifacts (tools/mfu_report.py reads the
            # trace dir on a jax-less node)
            for fname, payload in (("mfu_opmap.json", opmap),
                                   ("mfu_roofline.json", table),
                                   ("mfu_window.json", w),
                                   ("mfu_ledger.json", led)):
                try:
                    with open(os.path.join(w["trace_dir"], fname),
                              "w") as f:
                        json.dump(payload, f)
                except (OSError, TypeError, ValueError) as e:
                    logger.warning("mfu artifact %s not written: %s",
                                   fname, e)
        if self.telemetry is not None:
            self.telemetry.recorder.record(
                "event", "mfu/ledger", step=w["step"],
                data={k: led[k] for k in
                      ("achieved_mfu", "roofline_mfu", "step_s",
                       "device_busy_s", "top_sinks")})
        if self.monitor.enabled:
            from ..monitor.telemetry import check_events

            self.monitor.write_events(
                check_events(mfu_mod.ledger_events(led, step=w["step"])))
        return led

    # ================================================================ eager path
    def forward(self, batch):
        """Loss-only forward (reference ``engine.forward:1781``); caches the batch
        for the subsequent :meth:`backward`."""
        if self._eval_fn is None:
            self._eval_fn = jax.jit(
                lambda p, b, r: self._loss_and_metrics(p, b, r,
                                                       train=False)[0])
        self.timers(FORWARD_GLOBAL_TIMER).start()
        self._last_batch = batch
        loss = self._eval_fn(self.params, batch,
                             jax.random.fold_in(self._rng, self.micro_steps))
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        self.losses = loss
        return loss

    def backward(self, loss=None, batch=None):
        """Accumulate gradients for one microbatch (reference ``engine.backward:
        1922``). JAX has no stored autograd graph, so grads are recomputed from the
        cached (or given) batch; the ``loss`` argument is accepted for loop parity
        and ignored."""
        if self._grad_fn is None:
            self._grad_fn = jax.jit(
                lambda p, b, r, s: self._micro_grads(p, b, r, s))
            # once per run: ported reference loops land here and silently
            # pay ~2x FLOPs (JAX has no stored autograd graph, so backward
            # recomputes the forward) — point them at the fused path
            logger.warning(
                "eager forward()/backward()/step() loop detected: backward "
                "recomputes the forward under JAX (~2x FLOPs). Prefer "
                "engine.train_batch(batch) — one fused jitted step with "
                "identical semantics (see docs/MIGRATING.md)")
        batch = batch if batch is not None else self._last_batch
        if batch is None:
            raise RuntimeError("backward() needs forward() first or an explicit batch")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        repl = self.topology.replicated()
        rng = jax.device_put(jax.random.fold_in(self._rng, self.micro_steps),
                             repl)
        # under offload the scaler lives host-side between steps
        scaler = jax.device_put(self.scaler_state, repl)
        loss_val, _, grads = self._grad_fn(self.params, batch, rng, scaler)
        if self._accum_grads is None:
            self._accum_grads = grads
        else:
            self._accum_grads = jax.tree_util.tree_map(jnp.add, self._accum_grads,
                                                       grads)
        self._accum_losses.append(loss_val)
        self._accum_count += 1
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss_val

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference ``engine.is_gradient_accumulation_boundary``."""
        return self._accum_count >= self.config.gradient_accumulation_steps

    def step(self):
        """Apply accumulated gradients (reference ``engine.step:2120`` →
        ``_take_model_step:2054``)."""
        if self._accum_grads is None:
            raise RuntimeError("step() before backward()")
        if self.offload_device is not None:
            self.timers(STEP_GLOBAL_TIMER).start()
            grads = jax.tree_util.tree_map(
                lambda g: g / float(self._accum_count), self._accum_grads)
            metrics = dict(self._host_step(grads))
            self.timers(STEP_GLOBAL_TIMER).stop()
            if self._accum_losses:
                metrics["loss"] = jnp.stack(self._accum_losses).mean()
            self._accum_grads, self._accum_count = None, 0
            self._accum_losses = []
            self.global_steps += 1
            if self.telemetry is not None:
                # eager-path step span: boundary-to-boundary wall (dur=None)
                self.telemetry.on_step_end(
                    self.global_steps, offload=self._pop_offload_stats())
            self._post_step(metrics)
            return metrics
        if self._apply_fn is None:
            def apply_fn(params, opt_state, scaler, grads, count):
                grads = jax.tree_util.tree_map(lambda g: g / count, grads)
                new_params, new_opt, new_scaler, finite, grad_norm, _ = \
                    self._apply_grads(params, opt_state, scaler, grads)
                return new_params, new_opt, new_scaler, {
                    "finite": finite, "grad_norm": grad_norm,
                    "loss_scale": new_scaler.scale}
            # grads donate too (donation-audit find): the accumulator is
            # dead after this call (_accum_grads is cleared below), and an
            # undonated fp32 grad tree is a full extra param-sized buffer
            self._apply_fn = jax.jit(apply_fn, donate_argnums=(0, 1, 2, 3))
        self.timers(STEP_GLOBAL_TIMER).start()
        self.params, self.opt_state, self.scaler_state, metrics = self._apply_fn(
            self.params, self.opt_state, self.scaler_state, self._accum_grads,
            float(self._accum_count))
        self.timers(STEP_GLOBAL_TIMER).stop()
        metrics = dict(metrics)
        if self._accum_losses:
            # mean over the accumulation window (matches the fused path's
            # losses.mean(), not just the last microbatch)
            metrics["loss"] = jnp.stack(self._accum_losses).mean()
        self._accum_grads = None
        self._accum_count = 0
        self._accum_losses = []
        self.global_steps += 1
        if self.telemetry is not None:
            # eager-path step span: boundary-to-boundary wall (dur=None) —
            # includes data/host time between steps, unlike the fused path's
            # measured step_dur
            self.telemetry.on_step_end(self.global_steps)
        self._post_step(metrics)
        return metrics

    def _pop_offload_stats(self) -> Optional[Dict[str, Any]]:
        """The offload pipeline's per-step ledger, consumed exactly once."""
        stats = getattr(self, "_last_offload_stats", None)
        self._last_offload_stats = None
        return stats

    # ================================================================ shared tail
    def _post_step(self, metrics: Dict[str, Any]):
        """Per-step host bookkeeping. Deliberately does NOT force a device sync:
        metric arrays are only pulled at print boundaries so host dispatch of step
        n+1 overlaps device compute of step n (the reference gets the same overlap
        from streams; blocking here would serialize the pipeline)."""
        self.tput_timer.stop(report_speed=True)
        if self.global_steps % self.config.steps_per_print == 0:
            if self.fp16_enabled and not bool(
                    np.asarray(jax.device_get(metrics["finite"]))):
                log_dist(f"overflow: skipped step {self.global_steps}, "
                         f"loss scale -> {self.get_loss_scale()}")
            loss = metrics.get("loss")
            log_dist(
                f"step={self.global_steps} "
                f"loss={float(jax.device_get(loss)) if loss is not None else float('nan'):.4f} "
                f"lr={self.get_lr():.3e} scale={self.get_loss_scale():.1f}")
        if self.monitor.enabled:
            # Buffer device scalars; device_get only at print boundaries so the
            # host never blocks on in-flight steps (reference gets the same
            # overlap from CUDA streams).
            samples = self.global_steps * self.config.train_batch_size
            ev = [("Train/Samples/train_loss", metrics["loss"], samples)
                  ] if "loss" in metrics else []
            ev.append(("Train/Samples/lr", ("__lr__", self.global_steps), samples))
            if self.fp16_enabled:
                ev.append(("Train/Samples/loss_scale", metrics["loss_scale"],
                           samples))
            self._pending_events.extend(ev)
            if self.global_steps % self.config.steps_per_print == 0:
                self._flush_monitor()
        if self.config.wall_clock_breakdown and \
                self.global_steps % self.config.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER])
        if self._sentinel is not None:
            # lag-deferred health verdicts (runtime/sentinel.py): enqueue
            # this step's device scalars; entries >= cfg.lag steps old have
            # retired on device, so their pull is not a pipeline stall
            self._sentinel.at_step_boundary(self.global_steps, metrics)
        if self._resilience is not None:
            # step boundary: the only point where every buffer is quiescent,
            # so a pending SIGTERM (or injected preemption) saves here
            self._resilience.at_step_boundary()

    def _flush_monitor(self):
        events = []
        for name, val, samples in self._pending_events:
            if isinstance(val, tuple) and val[0] == "__lr__":
                try:
                    val = self.lr_schedule(val[1])
                except TypeError:
                    val = self.get_lr()
            events.append((name, float(jax.device_get(val)), samples))
        self._pending_events = []
        # degradation visibility: surface changed resilience counters (I/O
        # retries, fallback loads, emergency saves, …) as monitor events so
        # operators see trouble brewing instead of discovering it at recovery
        from ..monitor.monitor import resilience_counters

        samples = self.global_steps * self.config.train_batch_size
        for name, value in resilience_counters.snapshot().items():
            if value and value != self._resilience_reported.get(name):
                self._resilience_reported[name] = value
                events.append((f"Resilience/{name}", value, samples))
        if self.telemetry is not None:
            # Goodput/*, Memory/*, Compile/*, Ckpt/* at every print boundary
            events.extend(self.telemetry.periodic_events(samples))
        if comms_logger.enabled:
            events.extend(comms_logger.summary_events(samples))
        if events:
            self.monitor.write_events(events)

    # ================================================================ accessors
    @property
    def skipped_steps(self) -> int:
        """Cumulative overflow-skipped steps, tracked on-device by the loss
        scaler (reads force a sync; use sparingly)."""
        return int(jax.device_get(self.scaler_state.overflows))

    def get_lr(self) -> float:
        lr = current_lr(self.opt_state)
        if lr is None:
            try:
                lr = self.lr_schedule(self.global_steps)
            except TypeError:
                return float("nan")
        return float(jax.device_get(lr))

    def get_loss_scale(self) -> float:
        return float(jax.device_get(self.scaler_state.scale))

    def get_global_grad_norm(self) -> Optional[float]:
        return None  # exposed per-step in train metrics

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def register_dataloader(self, loader):
        """Attach the loader feeding ``train_batch`` so its iterator state
        (epoch/offset/seed — ``dataloader.state_dict``) rides checkpoint
        meta: resumes continue the stream instead of silently replaying or
        skipping data, and the sentinel's rollback can rewind it.
        ``initialize()`` registers the loader it builds automatically."""
        self._dataloader = loader
        return loader

    # ================================================================ resilience
    def enable_preemption_handling(self, save_dir: str,
                                   install_signal_handlers: bool = True,
                                   exit_fn: Optional[Callable[[int], None]]
                                   = None):
        """Arm preemption-aware checkpointing: SIGTERM/SIGINT (or an injected
        ``preempt_at_step`` fault) triggers an emergency ``save_checkpoint``
        into ``save_dir`` at the next step boundary, then exits with
        ``resilience.PREEMPTION_EXIT_CODE`` — which the elastic agent treats
        as a free restart. Returns the installed
        :class:`~.resilience.ResilienceManager`."""
        from .resilience import ResilienceManager

        self._resilience = ResilienceManager(self, save_dir, exit_fn=exit_fn)
        if install_signal_handlers:
            self._resilience.install()
        return self._resilience

    # ================================================================ checkpoint
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        save_latest: bool = True) -> str:
        """Sharded checkpoint save (reference ``engine.save_checkpoint:3050``:
        mp-rank module files + per-DP-rank ZeRO shards + ``latest`` tag file —
        here one orbax sharded tree serves all topologies), through the
        configured checkpoint engine (sync native, or the async Nebula-analog
        that returns after the host snapshot)."""
        if self.telemetry is not None:
            with self.telemetry.ckpt_span("save", step=self.global_steps):
                return self._save_checkpoint_impl(save_dir, tag, client_state,
                                                  save_latest)
        return self._save_checkpoint_impl(save_dir, tag, client_state,
                                          save_latest)

    def _save_checkpoint_impl(self, save_dir: str, tag: Optional[str],
                              client_state: Optional[Dict],
                              save_latest: bool) -> str:
        tag = tag or f"global_step{self.global_steps}"
        self._validate_tag(tag)
        path = os.path.join(save_dir, tag)
        if self._mh_offload is not None:
            # per-host master/moment shards reassemble into global arrays;
            # orbax writes them multi-controller like any sharded tree
            state = {"params": self._mh_offload.master_global_tree(),
                     "opt_state": self._mh_offload.moments_global_tree(),
                     "scaler": self.scaler_state}
        elif self.offload_device is not None:
            # persist the fp32 master copy (device params are lossy bf16)
            if self._swapper is not None and self.opt_state is None:
                self._swap_in_opt_state()
            state = {"params": self.master_params, "opt_state": self.opt_state,
                     "scaler": self.scaler_state}
        else:
            state = {"params": self.params, "opt_state": self.opt_state,
                     "scaler": self.scaler_state}
        meta = {"global_steps": self.global_steps, "micro_steps": self.micro_steps,
                "skipped_steps": self.skipped_steps,
                "config": {"zero_stage": self.zero_stage},
                "client_state": client_state or {}}
        if self.curriculum_scheduler is not None:
            meta["curriculum"] = self.curriculum_scheduler.state_dict()
        if self.random_ltd_scheduler is not None:
            meta["random_ltd"] = self.random_ltd_scheduler.state_dict()
        if self.qat_scheduler is not None:
            meta["qat"] = self.qat_scheduler.state_dict()
        if self._dataloader is not None and \
                hasattr(self._dataloader, "state_dict"):
            # iterator position rides the meta: a resume continues the data
            # stream where this save left it (and the sentinel's rollback
            # rewinds it deterministically)
            meta["dataloader"] = self._dataloader.state_dict()
        if self._sentinel is not None:
            meta["sentinel"] = self._sentinel.state_dict()
        post_commit = None
        keep = self.config.checkpoint.keep_last_n
        if keep and self._fi_rank == 0:
            from ..checkpoint.engine import rotate_checkpoints

            # rotation rides the engine's post-commit hook so it only ever
            # runs once the new tag is durable (async: on the worker thread)
            post_commit = lambda: rotate_checkpoints(save_dir, keep)  # noqa: E731
        self.checkpoint_engine.save(
            path, state, meta,
            latest_file=(os.path.join(save_dir, LATEST_FILE)
                         if save_latest else None),
            tag=tag, post_commit=post_commit)
        if self._swapper is not None:
            self._swap_out_opt_state()
        if self._sentinel is not None:
            # the tag enters the last-good promotion queue; it is promoted
            # only once K healthy steps beyond it are observed
            self._sentinel.note_checkpoint(tag, self.global_steps, save_dir)
        log_dist(f"saved checkpoint {path} "
                 f"({self.checkpoint_engine.name} engine)")
        return path

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True
                        ) -> Tuple[Optional[str], Dict]:
        """Restore (reference ``engine.load_checkpoint:2688``). Resharding-on-load:
        orbax restores into the *current* shardings, so a checkpoint written on any
        topology loads on any other — the capability the reference needs universal
        checkpoints for.

        A tag that passes :func:`~..checkpoint.engine.verify_tree` but tears
        between verification and read (raising
        ``CheckpointCorruptionError``) is quarantined and resolution retried
        on the remaining history — the engine path recovers from the same
        verified-then-torn race :func:`~..checkpoint.engine.load_latest_valid`
        does. An explicitly requested ``tag`` is never walked past: its
        corruption propagates to the caller."""
        from ..checkpoint.engine import (CheckpointCorruptionError,
                                         quarantine_tag)

        while True:
            try:
                return self._load_checkpoint_once(load_dir, tag,
                                                  load_optimizer_states)
            except CheckpointCorruptionError as e:
                if tag is not None:
                    raise
                from ..monitor.monitor import resilience_counters

                logger.warning("checkpoint %s corrupt on read (%s); "
                               "quarantining and retrying resolution",
                               e.path, e.reason)
                resilience_counters.incr("corrupt_tags_skipped")
                quarantine_tag(e.path)

    def _load_checkpoint_once(self, load_dir: str, tag: Optional[str],
                              load_optimizer_states: bool
                              ) -> Tuple[Optional[str], Dict]:
        load_tree = self.checkpoint_engine.load
        # before resolving `latest`: an async save may still be writing it
        self.checkpoint_engine.wait()
        if self._fi_rank == 0:
            # a worker killed mid-save before this restart left .staging-*
            # orphans (and possibly a torn-pod tag) behind; resume is the
            # natural sweep point, and pod rank 0 owns shared-dir hygiene
            from ..checkpoint.ckpt_engine import sweep_staging_dirs

            sweep_staging_dirs(load_dir)
        if tag is None:
            tag = self._resolve_resume_tag(load_dir)
            if tag is None:
                return None, {}
        path = os.path.join(load_dir, tag)
        if glob_mod.glob(os.path.join(path, "mp_rank_*_model_states.pt")):
            # a REFERENCE-format checkpoint (torch .pt layout): route to the
            # importer so DeepSpeed users' existing checkpoints just load
            from ..checkpoint.ds_import import load_deepspeed_checkpoint

            got = load_deepspeed_checkpoint(
                self, load_dir, tag,
                load_optimizer_states=load_optimizer_states)
            return os.path.join(load_dir, got), {}
        repl = self.topology.replicated()
        scaler_sh = jax.tree_util.tree_map(lambda _: repl, self.scaler_state)
        if self._mh_offload is not None:
            mh = self._mh_offload
            # shape-only template — moments_global_tree() would read the
            # whole optimizer state off NVMe just to learn shapes
            mom = mh.moments_template_tree()
            template = {
                "params": (mh.master_global_tree(), mh.shard_shardings),
                "opt_state": (mom, {"m": mh.shard_shardings,
                                    "v": mh.shard_shardings,
                                    "step": repl}),
                "scaler": (self.scaler_state, scaler_sh)}
            state, meta = load_tree(path, template)
            mh.load_state(state["params"],
                          state["opt_state"] if load_optimizer_states
                          else None)
            if load_optimizer_states:
                # back to host-numpy residence (see _init_offload): the
                # restore device_put the scaler to the mesh like any leaf
                from .loss_scaler import host_loss_scale_state

                self.scaler_state = host_loss_scale_state(state["scaler"])
            self.params = self._mh_push(mh.master_global_tree())
        elif self.offload_device is not None:
            if self._swapper is not None and self.opt_state is None:
                self._swap_in_opt_state()  # template needs the live tree
            cpu = self._cpu_device
            template = {"params": (self.master_params,
                                   jax.tree_util.tree_map(lambda _: cpu,
                                                          self.master_params)),
                        "opt_state": (self.opt_state,
                                      jax.tree_util.tree_map(lambda _: cpu,
                                                             self.opt_state)),
                        "scaler": (self.scaler_state, scaler_sh)}
            state, meta = load_tree(path, template)
            self.master_params = state["params"]
            if load_optimizer_states:
                self.opt_state = state["opt_state"]
                self.scaler_state = state["scaler"]
            self.params = self._push_params_to_device(self.master_params)
            if self._swapper is not None:
                self._swap_out_opt_state()
        else:
            template = {"params": (self.params, self.param_shardings),
                        "opt_state": (self.opt_state, self.opt_shardings),
                        "scaler": (self.scaler_state, scaler_sh)}
            state, meta = load_tree(path, template)
            self.params = state["params"]
            if load_optimizer_states:
                self.opt_state = state["opt_state"]
                self.scaler_state = state["scaler"]
        self.global_steps = meta.get("global_steps", 0)
        self.micro_steps = meta.get("micro_steps", 0)
        if self.curriculum_scheduler is not None and "curriculum" in meta:
            self.curriculum_scheduler.load_state_dict(meta["curriculum"])
        if self.random_ltd_scheduler is not None and "random_ltd" in meta:
            self.random_ltd_scheduler.load_state_dict(meta["random_ltd"])
        if self.qat_scheduler is not None and "qat" in meta:
            self.qat_scheduler.load_state_dict(meta["qat"])
            self._qat_bits, _ = self.qat_scheduler.update(self.global_steps)
            self._train_batch_fn = None  # retrace at the restored precision
            self._eval_fn = None
            self._grad_fn = None
        if self._dataloader is not None and "dataloader" in meta and \
                hasattr(self._dataloader, "load_state_dict"):
            self._dataloader.load_state_dict(meta["dataloader"])
        if self._sentinel is not None and "sentinel" in meta:
            self._sentinel.load_state_dict(meta["sentinel"])
        # skipped_steps rides in scaler_state.overflows, restored above
        log_dist(f"loaded checkpoint {path}")
        return path, meta.get("client_state", {})

    def _resolve_resume_tag(self, load_dir: str) -> Optional[str]:
        """Which tag to resume from: whatever ``latest`` names if it
        verifies, else the newest tag in history that does — a torn newest
        checkpoint costs one save interval, not the run. ``None`` when the
        directory holds nothing loadable.

        Shallow verification only (meta/index parse + file sizes): the
        chosen tag is immediately read by ``load_tree``, which checks every
        leaf's crc32 and raises ``CheckpointCorruptionError`` on mismatch —
        deep-verifying here would stream a multi-GB checkpoint twice on the
        restart critical path."""
        from ..checkpoint.engine import _read_latest, find_latest_valid_tag
        from ..monitor.monitor import resilience_counters

        pointed = _read_latest(load_dir)
        if pointed is not None and glob_mod.glob(
                os.path.join(load_dir, pointed, "mp_rank_*_model_states.pt")):
            # a REFERENCE-format (torch .pt layout) checkpoint carries no
            # dstpu manifest to verify; hand it to the importer untouched
            return self._agree_resume_tag(pointed)
        tag, skipped = find_latest_valid_tag(load_dir, deep=False)
        for skipped_tag, reason in skipped:
            logger.warning("skipping corrupt checkpoint %s: %s",
                           os.path.join(load_dir, skipped_tag), reason)
            resilience_counters.incr("corrupt_tags_skipped")
        tag = self._agree_resume_tag(tag)
        if tag is None:
            logger.warning("no loadable checkpoint in %s; nothing loaded",
                           load_dir)
            return None
        if tag != pointed or skipped:
            resilience_counters.incr("fallback_loads")
            logger.warning("fallback load: resuming %s (latest pointer was "
                           "%r)", os.path.join(load_dir, tag), pointed)
        return tag

    # one fixed-size slot per rank: the agreement collective must have a
    # static shape, so tags are padded/truncated to this many bytes
    _TAG_AGREE_BYTES = 256

    def _agree_resume_tag(self, tag: Optional[str]) -> Optional[str]:
        """Barrier-agreed resume tag: every rank allgathers its locally
        resolved candidate and adopts rank 0's. Resolution reads a shared
        directory, so ranks *usually* agree — but a save/quarantine racing
        a restart can split the view, and a pod whose ranks resume
        different steps silently diverges forever. The allgather doubles
        as the resume barrier: no rank starts loading until every rank has
        resolved. Single-process: identity."""
        if jax.process_count() == 1:
            return tag
        from jax.experimental import multihost_utils  # pragma: no cover

        buf = np.zeros(self._TAG_AGREE_BYTES, np.uint8)
        enc = (tag or "").encode()[:self._TAG_AGREE_BYTES]
        buf[:len(enc)] = np.frombuffer(enc, np.uint8)
        rows = np.asarray(multihost_utils.process_allgather(buf))
        agreed = bytes(rows.reshape(jax.process_count(), -1)[0]) \
            .rstrip(b"\x00").decode() or None
        if agreed != tag:
            from ..monitor.monitor import resilience_counters

            logger.warning(
                "resume-tag divergence: this rank resolved %r but the pod "
                "agreed on rank 0's %r — adopting the pod's choice", tag,
                agreed)
            resilience_counters.incr("fallback_loads")
        return agreed

    def save_16bit_model(self, save_dir: str,
                         checkpoint_name: str = "mp_rank_00_model_states.pt"
                         ) -> str:
        """Gather full (unsharded) weights and write one bf16 state-dict file
        (reference ``zero_gather_16bit_weights_on_model_save`` → engine
        ``save_16bit_model``, ``engine.py:771``). The gather the reference does
        with ZeRO-3 collectives is a host ``device_get`` of the logical array
        here — XLA assembles shards transparently."""
        import torch

        os.makedirs(save_dir, exist_ok=True)
        out = os.path.join(save_dir, checkpoint_name)
        from ..checkpoint.engine import _leaf_paths

        names = _leaf_paths(self.params)
        leaves = jax.tree_util.tree_leaves(self.params)
        sd = {}
        for name, leaf in zip(names, leaves):
            arr = np.asarray(jax.device_get(leaf))
            # jnp.issubdtype: ml_dtypes bfloat16 is not np.floating
            if jnp.issubdtype(arr.dtype, jnp.floating):
                # torch has no bfloat16 numpy bridge: go through fp32 view
                sd[name] = torch.from_numpy(
                    np.ascontiguousarray(arr.astype(np.float32))).bfloat16()
            else:
                sd[name] = torch.from_numpy(np.ascontiguousarray(arr))
        torch.save(sd, out)
        log_dist(f"saved 16-bit model to {out}")
        return out

    def _validate_tag(self, tag: str):
        """Tag agreement across processes (reference ``_checkpoint_tag_validation:
        3033`` — bf16 allreduce of the tag hash)."""
        mode = self.config.checkpoint.tag_validation
        if mode == "Ignore" or jax.process_count() == 1:
            return
        # multi-controller: compare a tag digest via a tiny device allreduce.
        # Must be deterministic across processes — Python's str hash is salted
        # per-process (PYTHONHASHSEED), so crc32 instead.
        import zlib

        h = float(zlib.crc32(tag.encode()) % (2 ** 16))
        arr = jnp.full((jax.local_device_count(),), h)
        total = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(arr)
        expect = h * jax.device_count()
        if not np.allclose(np.asarray(total)[0], expect):
            msg = f"checkpoint tag {tag!r} differs across ranks"
            if mode == "Fail":
                raise RuntimeError(msg)
            logger.warning(msg)

    # ================================================================ misc
    def eval_batch(self, batch):
        """Loss on a batch WITHOUT touching training state (does not cache the
        batch for backward(), unlike :meth:`forward`)."""
        if self._eval_fn is None:
            self._eval_fn = jax.jit(
                lambda p, b, r: self._loss_and_metrics(p, b, r,
                                                       train=False)[0])
        return self._eval_fn(self.params, batch,
                             jax.random.fold_in(self._rng, self.micro_steps))

    def __call__(self, batch):
        return self.forward(batch)
