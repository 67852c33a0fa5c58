"""Roofline partition of a compiled step: per-region FLOPs/bytes vs peaks.

The jaxpr half of the MFU ledger (``monitor/mfu.py`` holds the stdlib
trace/join half): walk the step's closed jaxpr (``jaxpr_walk`` — scan
bodies multiply by trip count, so the layer stack costs L×), attribute
every equation to the ``mfu.<region>`` named-scope label recorded in its
``source_info.name_stack`` (forward AND backward: transpose/jvp wrappers
preserve the scope — ``transpose(jvp(mfu.attn))`` still names ``attn``),
and price each region against a device peak-spec:

* analytic FLOPs per region (``profiling/flops_profiler.eqn_flops`` — the
  same rules the engine's FLOPS profiler counts with);
* HBM bytes per region — a perfect-fusion FLOOR: matmuls/convolutions/
  reductions/data movement count operand + result bytes (those arrays must
  stream through memory), elementwise ops count result bytes only (XLA
  fuses their inputs into the producer). Optimistic by construction, which
  is what "roofline-achievable" must be — real traffic sits between this
  floor and the unfused sum.
* comm bytes per region — in-jaxpr collective payloads (shard_map bodies:
  ring/ulysses/zeropp). Partitioner-INSERTED collectives never appear in a
  jaxpr; their bytes come from the HLO census (``analysis/collectives.py``)
  and land in the derived ``collective`` region via ``census_bytes``.

Each region's roofline-achievable time is ``max(flops/peak, bytes/hbm_bw,
comm/ici_bw)`` and the max's argument is the bound-by verdict — the
"name where the step time goes" instrument the ROADMAP's MFU item needs
before any real-TPU run can be interpreted.
"""
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import numpy as np

from ..monitor.mfu import REGIONS, region_of  # stdlib-only module

#: in-jaxpr collective primitives (explicit shard_map bodies); payload =
#: result bytes. The partitioner's own collectives are censused from HLO.
COLLECTIVE_PRIMS = frozenset({
    "psum", "all_gather", "all_to_all", "ppermute", "psum_scatter",
    "pmax", "pmin", "reduce_scatter",
})


@dataclass(frozen=True)
class DeviceSpec:
    """Per-chip peaks. ``peak_flops`` is the dense bf16 (or fp32 for the
    CPU sim) matmul peak; ``hbm_gbps`` main-memory bandwidth; ``ici_gbps``
    per-chip interconnect bandwidth (one direction, all links)."""
    name: str
    peak_flops: float
    hbm_gbps: float
    ici_gbps: float

    def as_dict(self) -> Dict[str, float]:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_gbps": self.hbm_gbps, "ici_gbps": self.ici_gbps}


#: Peak-spec registry. TPU numbers are the published per-chip peaks
#: (bf16 dense / HBM BW / aggregate ICI per chip); add a device by adding a
#: row here and (if its ``device_kind`` string is new) a match in
#: :func:`device_spec` — docs/observability.md documents the procedure.
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    "tpu-v4": DeviceSpec("tpu-v4", 275e12, 1228.0, 300.0),
    "tpu-v5e": DeviceSpec("tpu-v5e", 197e12, 819.0, 200.0),
    "tpu-v5p": DeviceSpec("tpu-v5p", 459e12, 2765.0, 600.0),
    "tpu-v6e": DeviceSpec("tpu-v6e", 918e12, 1640.0, 400.0),
    # CPU-sim entry: replaced by a measured calibration (see
    # calibrate_cpu_spec) the first time it is asked for, so CPU-sim MFU
    # numbers mean "fraction of what THIS host's XLA actually peaks at",
    # not fraction of an arbitrary constant.
    "cpu-sim": DeviceSpec("cpu-sim", 5e10, 10.0, 1.0),
}

_cpu_calibrated: Optional[DeviceSpec] = None


def calibrate_cpu_spec(force: bool = False) -> DeviceSpec:
    """Measured CPU-sim peaks (cached process-wide): a 512³ f32 matmul
    chain prices ``peak_flops``, a large copy prices ``hbm_gbps``. Coarse
    (one shape, one dtype) but honest — the roofline verdicts on the CPU
    sim then compare against what this host can actually do."""
    global _cpu_calibrated
    if _cpu_calibrated is not None and not force:
        return _cpu_calibrated
    import time

    import jax
    import jax.numpy as jnp

    n, iters = 512, 8

    @jax.jit
    def chain(x):
        for _ in range(iters):
            x = x @ x
        return x

    x = jnp.ones((n, n), jnp.float32)
    chain(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    chain(x).block_until_ready()
    dt = max(time.perf_counter() - t0, 1e-9)
    peak = 2.0 * n ** 3 * iters / dt

    m = 1 << 22  # 4M f32 = 16 MiB through the copy

    @jax.jit
    def copy(x):
        return x + 1.0

    y = jnp.ones((m,), jnp.float32)
    copy(y).block_until_ready()
    t0 = time.perf_counter()
    copy(y).block_until_ready()
    dt = max(time.perf_counter() - t0, 1e-9)
    bw = 2.0 * m * 4 / dt / 1e9  # read + write
    _cpu_calibrated = DeviceSpec("cpu-sim", peak, bw,
                                 DEVICE_SPECS["cpu-sim"].ici_gbps)
    return _cpu_calibrated


def device_spec(device: Any = None,
                calibrate_cpu: bool = True) -> DeviceSpec:
    """Spec for a jax device (default: ``jax.devices()[0]``), matched on
    ``device_kind``/platform. A TPU generation that is not in
    ``DEVICE_SPECS`` is an error, never another chip's peaks; CPU returns
    the calibrated CPU-sim entry."""
    import jax

    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return (calibrate_cpu_spec() if calibrate_cpu
                else DEVICE_SPECS["cpu-sim"])
    kind = (getattr(device, "device_kind", "") or "").lower()
    for tag, key in (("v6", "tpu-v6e"), ("v5p", "tpu-v5p"),
                     ("v5", "tpu-v5e"), ("v4", "tpu-v4")):
        if tag in kind:
            return DEVICE_SPECS[key]
    raise KeyError(
        f"no peak spec for TPU device_kind {kind!r}: add its published "
        f"peaks (with their source) to analysis/roofline.py DEVICE_SPECS")


# ----------------------------------------------------------- region costing
def _aval_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0.0
    try:
        itemsize = np.dtype(getattr(aval, "dtype", np.float32)).itemsize
    except TypeError:
        # extended dtypes (PRNG keys) have no numpy equivalent; 4 bytes per
        # element is close enough for arrays this small
        itemsize = 4
    return float(math.prod(shape)) * itemsize if shape else float(itemsize)


def _eqn_region(eqn) -> Optional[str]:
    # ONE extraction rule for both halves of the ledger: the jaxpr name
    # stack and the HLO op_name metadata are the same path syntax, so the
    # analytic and measured views must share monitor/mfu.region_of — a
    # local re-implementation could silently drift and mis-join regions
    return region_of(str(getattr(eqn.source_info, "name_stack", "") or ""))


def region_costs(closed_jaxpr) -> Dict[str, Dict[str, float]]:
    """Per-region analytic cost table ``{region: {"flops", "hbm_bytes",
    "comm_bytes", "n_eqns"}}`` over the recursive equation stream. Scoped
    regions come from the name stack; in-jaxpr collectives override to
    ``collective``; everything else is ``other``."""
    from ..profiling.flops_profiler import _CHEAP, eqn_flops
    from .jaxpr_walk import iter_eqns

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    out: Dict[str, Dict[str, float]] = {
        r: {"flops": 0.0, "hbm_bytes": 0.0, "comm_bytes": 0.0, "n_eqns": 0}
        for r in REGIONS if r != "host"}
    for eqn, mult in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMS:
            region = "collective"
            comm = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        else:
            region = _eqn_region(eqn) or "other"
            comm = 0.0
        row = out[region]
        f = eqn_flops(eqn)
        if f is not None:
            row["flops"] += f * mult
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        if prim in _CHEAP:
            # elementwise: inputs fuse into their producer — result only
            nbytes = out_bytes
        else:
            nbytes = out_bytes + sum(_aval_bytes(v.aval) for v in eqn.invars
                                     if hasattr(v, "aval"))
        row["hbm_bytes"] += mult * nbytes
        row["comm_bytes"] += comm * mult
        row["n_eqns"] += 1
    return out


def roofline_table(costs: Dict[str, Dict[str, float]],
                   spec: DeviceSpec,
                   census_bytes: Optional[int] = None) -> Dict[str, Any]:
    """Evaluate per-region costs against a device spec: each region's
    achievable time is the max of its compute, memory and comm terms and
    ``bound_by`` names the binding one. ``census_bytes`` (the HLO
    collective census total, ``analysis/collectives.py``) is added to the
    ``collective`` region — partitioner-inserted traffic the jaxpr can't
    see. Serializes to the ``monitor/mfu.ledger`` roofline contract."""
    regions: Dict[str, Dict[str, Any]] = {}
    total_flops = total_achievable = 0.0
    costs = {k: dict(v) for k, v in costs.items()}
    if census_bytes:
        col = costs.setdefault(
            "collective",
            {"flops": 0.0, "hbm_bytes": 0.0, "comm_bytes": 0.0, "n_eqns": 0})
        col["comm_bytes"] += float(census_bytes)
    for name, c in costs.items():
        t_compute = c["flops"] / spec.peak_flops if spec.peak_flops else 0.0
        t_memory = c["hbm_bytes"] / (spec.hbm_gbps * 1e9) \
            if spec.hbm_gbps else 0.0
        t_comm = c["comm_bytes"] / (spec.ici_gbps * 1e9) \
            if spec.ici_gbps else 0.0
        terms = {"compute": t_compute, "memory": t_memory, "comm": t_comm}
        bound = max(terms, key=terms.get)
        achievable = terms[bound]
        regions[name] = {
            "flops": c["flops"], "hbm_bytes": c["hbm_bytes"],
            "comm_bytes": c["comm_bytes"],
            "t_compute": t_compute, "t_memory": t_memory, "t_comm": t_comm,
            "achievable_s": achievable,
            "bound_by": bound if achievable > 0 else None,
        }
        total_flops += c["flops"]
        total_achievable += achievable
    return {
        "device": spec.name,
        "spec": spec.as_dict(),
        "regions": regions,
        "total_flops": total_flops,
        "total_achievable_s": total_achievable,
    }
