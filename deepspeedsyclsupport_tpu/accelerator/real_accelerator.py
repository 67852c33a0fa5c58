"""Accelerator selection registry.

TPU-native analog of the reference's ``accelerator/real_accelerator.py:23,51-192``:
env-var override (theirs: ``DS_ACCELERATOR``; ours: ``DSTPU_ACCELERATOR``) plus
import-probing auto-detect (theirs probes ipex/torch_npu/mps; ours probes the live JAX
platform). One process-global accelerator object, settable for tests.
"""
import os
from typing import Optional

from .abstract_accelerator import Accelerator

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu", "gpu"]

_ACCELERATOR: Optional[Accelerator] = None


class _JaxAccelerator(Accelerator):
    """Concrete accelerator bound to one JAX platform string."""

    def __init__(self, platform_name: str):
        self._platform = platform_name

    def name(self) -> str:
        return self._platform

    def communication_backend_name(self) -> str:
        return {"tpu": "ici", "gpu": "nccl"}.get(self._platform, "xla-cpu")

    def devices(self):
        """This platform's devices. Raises ``RuntimeError`` (jax's own) when
        the platform is absent — an accelerator never answers with another
        backend's devices or an empty list."""
        import jax

        return jax.devices(self._platform)

    def is_available(self) -> bool:
        try:
            return len(self.devices()) > 0
        except RuntimeError:
            return False


class TpuAccelerator(_JaxAccelerator):
    def __init__(self):
        super().__init__("tpu")


class CpuAccelerator(_JaxAccelerator):
    def __init__(self):
        super().__init__("cpu")

    def preferred_dtype(self):
        import jax.numpy as jnp

        # CPU simulation keeps bf16 to mirror TPU numerics in tests.
        return jnp.bfloat16


class GpuAccelerator(_JaxAccelerator):
    def __init__(self):
        super().__init__("gpu")


def _detect() -> Accelerator:
    """Honor DSTPU_ACCELERATOR, else the live jax platform (tpu or gpu). The
    CPU is an accelerator only when asked for — ``DSTPU_ACCELERATOR=cpu`` or
    ``JAX_PLATFORMS=cpu``; jax quietly falling back to it because no
    accelerator answered is an error, not a CPU run."""
    override = os.environ.get("DSTPU_ACCELERATOR")
    if override:
        if override not in SUPPORTED_ACCELERATOR_LIST:
            raise ValueError(
                f"DSTPU_ACCELERATOR={override!r} not in {SUPPORTED_ACCELERATOR_LIST}")
        return {"tpu": TpuAccelerator, "cpu": CpuAccelerator, "gpu": GpuAccelerator}[override]()

    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return TpuAccelerator()
    if platform in ("gpu", "cuda", "rocm"):
        return GpuAccelerator()
    if jax.config.jax_platforms == "cpu":
        return CpuAccelerator()
    raise RuntimeError(
        f"jax found no accelerator (default backend {platform!r}); set "
        f"JAX_PLATFORMS=cpu or DSTPU_ACCELERATOR=cpu to run on the CPU "
        f"deliberately")


def get_accelerator() -> Accelerator:
    """Process-global accelerator (reference: ``real_accelerator.py:51``)."""
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = _detect()
    return _ACCELERATOR


def set_accelerator(acc: Accelerator) -> None:
    """Explicit override (reference: ``real_accelerator.py:195``)."""
    global _ACCELERATOR
    _ACCELERATOR = acc


def reset_accelerator() -> None:
    global _ACCELERATOR
    _ACCELERATOR = None
