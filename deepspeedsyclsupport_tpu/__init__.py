"""deepspeedsyclsupport_tpu — a TPU-native distributed training + inference framework.

Brand-new JAX/XLA/Pallas/pjit design with the capabilities of the reference DeepSpeed
0.12.7 fork (delock/DeepSpeedSYCLSupport): one JSON-config engine composing DP / ZeRO-
style FSDP / TP / PP / Ulysses-SP / MoE-EP over a named TPU mesh, bf16/fp16 training,
sharded+universal checkpoints, a FastGen-class paged-KV serving engine, and the aux ring
(profiling, comm logging, monitoring, elasticity, autotuning).

Public API parity (reference ``deepspeed/__init__.py``):
  * :func:`initialize`        — ``deepspeed.initialize``        (``__init__.py:64``)
  * :func:`init_inference`    — ``deepspeed.init_inference``    (``__init__.py:269``)
  * :func:`init_distributed`  — ``deepspeed.init_distributed``
  * :mod:`comm`               — ``deepspeed.comm``
"""
from .version import __version__
from .accelerator import get_accelerator, set_accelerator
from .comm import init_distributed
from .comm.topology import MeshTopology, build_topology, get_world_topology

__all__ = [
    "__version__",
    "get_accelerator",
    "set_accelerator",
    "init_distributed",
    "MeshTopology",
    "build_topology",
    "get_world_topology",
    "initialize",
    "init_inference",
    "DeepSpeedTransformerLayer",
    "DeepSpeedTransformerConfig",
]


def __getattr__(name):
    # top-level aliases the reference exports from deepspeed/__init__.py,
    # resolved lazily so importing the package stays light
    if name in ("DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig"):
        from .ops import transformer as _t

        return getattr(_t, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def initialize(*args, **kwargs):
    """Create a training :class:`~deepspeedsyclsupport_tpu.runtime.engine.Engine`
    (reference: ``deepspeed.initialize``, ``deepspeed/__init__.py:64``)."""
    from .runtime.engine import initialize as _impl

    return _impl(*args, **kwargs)


def init_inference(*args, **kwargs):
    """Create an inference engine (reference: ``deepspeed.init_inference``,
    ``deepspeed/__init__.py:269``)."""
    from .inference.engine import init_inference as _impl

    return _impl(*args, **kwargs)


# the set-up ledger is always on (monitor/telemetry.py): one jax.monitoring
# listener that costs an append per compile event and nothing per step
from .monitor import telemetry as _telemetry  # noqa: E402

_telemetry.install_compile_listener()
