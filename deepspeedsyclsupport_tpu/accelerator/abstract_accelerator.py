"""Abstract accelerator interface.

TPU-native analog of the reference's pluggable-backend seam
(``accelerator/abstract_accelerator.py:10-277`` — ``DeepSpeedAccelerator`` ABC with ~60
abstract methods for device mgmt, RNG, streams, memory stats, dtype support, op builders).

In a JAX design most of those methods collapse: there are no user-visible streams or
pinned-memory pools (XLA owns scheduling and transfers), and kernels are Pallas functions
rather than JIT-compiled C++ extensions. What survives is the *seam itself*: every device
touch in the runtime goes through :func:`get_accelerator`, so swapping TPU ⇄ CPU-sim ⇄ GPU
is one registry change, exactly like the reference swaps cuda/xpu/cpu backends.
"""
import abc
from typing import Any, Dict, List, Optional, Sequence


class Accelerator(abc.ABC):
    """Device backend interface: naming, devices, dtypes, memory, RNG, collectives name.

    Mirrors the surface of the reference ABC that is meaningful under XLA. Methods that
    exist purely because of CUDA semantics (streams, events, graph capture, pinned
    allocators) are intentionally absent: XLA's async dispatch plays the role of streams,
    and compiled executables play the role of CUDA graphs.
    """

    # ------------------------------------------------------------------ identity
    @abc.abstractmethod
    def name(self) -> str:
        """Backend name: 'tpu' or 'cpu' (simulated mesh)."""

    @abc.abstractmethod
    def communication_backend_name(self) -> str:
        """Name of the collective transport (reference: nccl/ccl/hccl).

        On TPU this is the ICI/DCN fabric driven by XLA collectives; on the CPU
        simulator it is the host 'gloo-like' XLA CPU collectives.
        """

    # ------------------------------------------------------------------ devices
    @abc.abstractmethod
    def devices(self) -> List[Any]:
        """All addressable jax devices for this backend."""

    def device_count(self) -> int:
        return len(self.devices())

    @abc.abstractmethod
    def is_available(self) -> bool:
        """True if this backend has at least one live device."""

    def current_device(self) -> Any:
        return self.devices()[0]

    def synchronize(self, tree: Any = None) -> None:
        """Block until async dispatch has drained (reference: device synchronize)."""
        import jax

        if tree is None:
            # effects_barrier waits for all in-flight computations.
            jax.effects_barrier()
        else:
            jax.block_until_ready(tree)

    # ------------------------------------------------------------------ dtypes
    def supported_dtypes(self) -> List[Any]:
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    def preferred_dtype(self) -> Any:
        """Default low-precision compute dtype (bf16 is TPU-native)."""
        import jax.numpy as jnp

        return jnp.bfloat16

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    # ------------------------------------------------------------------ memory
    def memory_stats(self, device: Optional[Any] = None) -> Dict[str, int]:
        """Per-device memory statistics (reference: memory_allocated/max_memory etc.)."""
        dev = device or self.current_device()
        stats = getattr(dev, "memory_stats", lambda: None)()
        return dict(stats) if stats else {}

    def available_memory(self, device: Optional[Any] = None) -> Optional[int]:
        stats = self.memory_stats(device)
        if "bytes_limit" in stats:
            return stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        return None

    def total_memory(self, device: Optional[Any] = None) -> Optional[int]:
        stats = self.memory_stats(device)
        return stats.get("bytes_limit")

    # ------------------------------------------------------------------ RNG
    def default_rng(self, seed: int):
        import jax

        return jax.random.PRNGKey(seed)

    # ------------------------------------------------------------------ introspection
    def device_kind(self) -> str:
        try:
            return self.devices()[0].device_kind
        except Exception:
            return "unknown"

    def platform(self) -> str:
        try:
            return self.devices()[0].platform
        except Exception:
            return self.name()

    def on_tpu(self) -> bool:
        return self.platform() == "tpu"


def literal_device_count(backend: Optional[str] = None) -> int:
    import jax

    return jax.device_count(backend) if backend else jax.device_count()
