"""TPU kernel library (Pallas) + native host ops — the analog of the
reference's ``csrc/`` + ``deepspeed/ops`` native-op layer (SURVEY.md §2.5).

Device compute ops (``flash_attention``; the serving path's paged kernels,
``paged_attention``, and beside them the grouped expert GEMM whose row tile
fits the expert, ``grouped_gemm``) dispatch from the model/engine level
and fall back to XLA-fused jnp references off-TPU. Host systems ops (async IO)
are C++ behind a C ABI, JIT-built and loaded through :mod:`.op_builder` — the
reference's ``OpBuilder.load()`` pattern without torch/pybind11.
"""
from .op_builder import ALL_OPS, AsyncIOBuilder, OpBuilder, get_op_builder  # noqa: F401
from .evoformer_attn import DS4Sci_EvoformerAttention  # noqa: F401
