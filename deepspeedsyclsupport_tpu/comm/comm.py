"""Collectives façade.

TPU-native analog of ``deepspeed/comm/comm.py`` (module-level collectives with the
``@timed_op`` profiling wrapper, ops at ``comm.py:222-521``, ``init_distributed:604``)
and the backends behind it (``comm/torch.py:99`` TorchBackend → NCCL,
``comm/ccl.py:34`` CCLBackend → oneCCL).

Design shift: the reference's collectives are *eager library calls* on torch tensors;
ours are *traced primitives* — ``jax.lax.{psum, all_gather, psum_scatter, all_to_all,
ppermute}`` over named mesh axes — that XLA lowers onto ICI/DCN and overlaps with
compute automatically. The façade therefore has two layers:

1. **Named-axis ops** (this module): thin wrappers usable inside ``shard_map``/``pjit``
   bodies, carrying the reference façade's op vocabulary, comms logging, and per-op
   kill-switch env flags (reference ``comm/torch.py:13-17`` ``DS_COMM_*_OFF``).
2. **Process bootstrap**: ``init_distributed()`` maps to
   ``jax.distributed.initialize`` (the analog of ``torch.distributed.init_process_group``
   rendezvous at ``comm/comm.py:604``), with env-based discovery.

The SPMD partitioner also inserts collectives implicitly from sharding specs; this
façade is for the *explicit* paths (pipeline p2p, MoE all-to-all, Ulysses, ZeRO grad
reduce inside shard_map) and for tests/debugging.
"""
import math
import os
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .comms_logging import comms_logger

__all__ = [
    "all_reduce", "all_gather", "reduce_scatter", "all_to_all", "hierarchical_all_to_all", "ppermute",
    "broadcast", "pmean", "axis_size", "axis_index", "send_recv_next",
    "send_recv_prev", "init_distributed", "is_initialized", "barrier",
    "get_world_size", "get_rank", "get_local_rank", "get_device_count",
    # reference-surface parity (root-based ops, p2p, coalesced, aliases)
    "reduce", "gather", "scatter", "p2p", "send", "recv",
    "all_reduce_coalesced", "all_gather_coalesced",
    "all_gather_into_tensor", "reduce_scatter_tensor", "all_to_all_single",
    "inference_all_reduce", "monitored_barrier", "new_group",
    "get_global_rank", "get_world_group", "get_all_ranks_from_group",
    "destroy_process_group",
]

_INITIALIZED = False
_DEFAULT_SLURM_PORT = 29500  # coordinator port when srun env names no port


# ---------------------------------------------------------------------------
# kill switches (reference: DS_COMM_{REDUCE_SCATTER,ALL_GATHER,...}_OFF,
# comm/torch.py:13-17) — turn a collective into identity for fault isolation.
# ---------------------------------------------------------------------------
def _off(op: str) -> bool:
    return os.environ.get(f"DSTPU_COMM_{op}_OFF", "").lower() in ("1", "true", "yes")


def _nbytes(x) -> int:
    try:
        return math.prod(int(s) for s in x.shape) * jnp.dtype(x.dtype).itemsize
    except Exception:
        return 0


def _log(op: str, axis, x):
    comms_logger.append(op, axis, _nbytes(x), tuple(getattr(x, "shape", ())))


# ---------------------------------------------------------------------------
# named-axis collectives (use inside shard_map / pjit with a Mesh installed)
# ---------------------------------------------------------------------------
def all_reduce(x, axis_name, op: str = "sum"):
    """Sum/max/min-reduce across a mesh axis (reference: ``comm.all_reduce``,
    ``comm/comm.py:494``)."""
    if _off("ALL_REDUCE"):
        return x
    _log("all_reduce", axis_name, x)
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    if op in ("avg", "mean"):
        return lax.pmean(x, axis_name)
    raise ValueError(f"unsupported reduce op {op!r}")


def pmean(x, axis_name):
    if _off("ALL_REDUCE"):
        return x
    _log("all_reduce_mean", axis_name, x)
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` across the mesh axis (reference:
    ``all_gather_into_tensor``, ``comm/comm.py:320``)."""
    if _off("ALL_GATHER"):
        return x
    _log("all_gather", axis_name, x)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis: int = 0):
    """Sum-reduce then scatter along ``axis`` (reference: ``reduce_scatter_tensor``,
    ``comm/comm.py:357``; ZeRO's grad-shard primitive ``stage_1_and_2.py:1004``)."""
    if _off("REDUCE_SCATTER"):
        return x
    _log("reduce_scatter", axis_name, x)
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, axis_name, split_axis: int, concat_axis: int, tiled: bool = True):
    """All-to-all (reference: ``all_to_all_single``, ``comm/comm.py:430``; the MoE
    dispatch primitive ``moe/sharded_moe.py:95`` and Ulysses ``sequence/layer.py:15``)."""
    if _off("ALL_TO_ALL"):
        return x
    _log("all_to_all", axis_name, x)
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def hierarchical_all_to_all(x, axis_name, group_size: int,
                            split_axis: int = 0, concat_axis: int = 0):
    """Two-hop all-to-all: intra-group exchange first, then inter-group.

    Drop-in equivalent of ``all_to_all(x, axis, split, concat, tiled=True)``
    decomposed the way the reference's hierarchical MoE dispatch does it
    (``utils/groups.py:356`` ``_get_local_all_to_all_group``): with N ranks
    in groups of ``group_size`` (a TPU slice / a node), every rank first
    exchanges within its group over fast links (ICI), then one exchange
    crosses groups (DCN) — cross-group messages per device drop from
    ``N − group_size`` to ``N / group_size − 1``, which is what makes MoE
    routing viable across slices.
    """
    if _off("ALL_TO_ALL"):
        return x
    n = lax.axis_size(axis_name)
    gs = int(group_size)
    if n % gs:
        raise ValueError(f"axis size {n} not divisible by group_size {gs}")
    ng = n // gs
    if gs == 1 or ng == 1:
        return all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)
    _log("hierarchical_all_to_all", axis_name, x)
    if x.shape[split_axis] % n:
        raise ValueError(f"split dim {x.shape[split_axis]} not divisible "
                         f"by axis size {n}")
    # parts [tg, tl, ...]: chunk (tg, tl) is destined for rank tg·gs + tl
    parts = jnp.moveaxis(
        x.reshape(x.shape[:split_axis] + (n, x.shape[split_axis] // n)
                  + x.shape[split_axis + 1:]), split_axis, 0)
    parts = parts.reshape((ng, gs) + parts.shape[1:])
    intra = [[g * gs + l for l in range(gs)] for g in range(ng)]
    inter = [[g * gs + l for g in range(ng)] for l in range(gs)]
    # hop 1 (ICI): z[tg, sl, ...] = source (G, sl)'s chunk (tg, my_l)
    z = lax.all_to_all(parts, axis_name, split_axis=1, concat_axis=1,
                       axis_index_groups=intra)
    # hop 2 (DCN): w[sg, sl, ...] = source (sg, sl)'s chunk (my_g, my_l)
    w = lax.all_to_all(z, axis_name, split_axis=0, concat_axis=0,
                       axis_index_groups=inter)
    w = w.reshape((n,) + w.shape[2:])           # source-major, = plain a2a
    out = jnp.moveaxis(w, 0, concat_axis)
    return out.reshape(out.shape[:concat_axis]
                       + (out.shape[concat_axis]
                          * out.shape[concat_axis + 1],)
                       + out.shape[concat_axis + 2:])


def ppermute(x, axis_name, perm: Sequence[tuple]):
    """Point-to-point permutation — the TPU p2p primitive under pipeline parallelism
    (reference: ``runtime/pipe/p2p.py`` send/recv)."""
    if _off("P2P"):
        return x
    _log("ppermute", axis_name, x)
    return lax.ppermute(x, axis_name, perm=perm)


def send_recv_next(x, axis_name, n: Optional[int] = None, wrap: bool = True):
    """Shift +1 along a mesh axis (stage i → i+1).

    ``wrap=True`` is a full ring (stage 0 receives stage n-1's value — collective
    rotations, ring attention). ``wrap=False`` drops the wraparound edge; ppermute
    zero-fills unlisted destinations, so stage 0 receives zeros — the pipeline p2p
    contract (reference: ``runtime/pipe/p2p.py`` send/recv to stage+1).
    """
    n = n or lax.axis_size(axis_name)
    pairs = [(i, (i + 1) % n) for i in range(n if wrap else n - 1)]
    return ppermute(x, axis_name, pairs)


def send_recv_prev(x, axis_name, n: Optional[int] = None, wrap: bool = True):
    """Shift -1 along a mesh axis (stage i → i-1); see :func:`send_recv_next`."""
    n = n or lax.axis_size(axis_name)
    pairs = [(i, (i - 1) % n) for i in (range(n) if wrap else range(1, n))]
    return ppermute(x, axis_name, pairs)


def broadcast(x, axis_name, src: int = 0):
    """Broadcast src's shard to all members of the axis (reference: ``comm.broadcast``,
    ``comm/comm.py:224``; engine param broadcast ``engine.py:1052``)."""
    if _off("BROADCAST"):
        return x
    _log("broadcast", axis_name, x)
    # ppermute is a strict permutation, so broadcast is select-then-psum: non-src
    # shards are replaced by zeros *before* the sum so NaN/Inf garbage on non-src
    # ranks (e.g. uninitialized params awaiting the broadcast) cannot poison it.
    contrib = jnp.where(lax.axis_index(axis_name) == src, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis_name)


def axis_size(axis_name) -> int:
    return lax.axis_size(axis_name)


def axis_index(axis_name):
    return lax.axis_index(axis_name)


# ---------------------------------------------------------------------------
# process bootstrap (reference: init_distributed comm/comm.py:604)
# ---------------------------------------------------------------------------
def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     auto_mpi_discovery: bool = True,
                     dist_init_required: Optional[bool] = None) -> bool:
    """Initialize multi-host JAX runtime.

    Single-host (the common test/benchmark path) is a no-op: JAX already sees
    all local devices. Multi-host reads env — JAX-native vars or the reference's
    RANK/WORLD_SIZE/MASTER_ADDR convention set by its launcher
    (``launcher/launch.py:132``) — and calls ``jax.distributed.initialize``.
    ``auto_mpi_discovery`` mirrors ``mpi_discovery`` (``comm/comm.py:673``) by reading
    OMPI env vars when the torch-style ones are absent.
    """
    global _INITIALIZED
    if _INITIALIZED or dist_init_required is False:
        return False

    env = os.environ
    coord = coordinator_address or env.get("COORDINATOR_ADDRESS")
    nprocs = num_processes if num_processes is not None else _int_env("NUM_PROCESSES")
    pid = process_id if process_id is not None else _int_env("PROCESS_ID")

    # torch-style env:// convention (reference launcher sets these)
    if coord is None and "MASTER_ADDR" in env:
        port = env.get("MASTER_PORT", "1234")
        coord = f"{env['MASTER_ADDR']}:{port}"
        nprocs = nprocs if nprocs is not None else _int_env("WORLD_SIZE")
        pid = pid if pid is not None else _int_env("RANK")

    # MPI discovery (reference: comm/comm.py:673). MPI env gives size/rank; the
    # coordinator must still be a bare host:port that process 0 can bind
    # (the ORTE HNP URI is mpirun's daemon, not a usable coordinator), so we
    # require DSTPU_COORDINATOR/MASTER_ADDR alongside MPI env.
    if auto_mpi_discovery and "OMPI_COMM_WORLD_SIZE" in env:
        nprocs = nprocs if nprocs is not None else int(env["OMPI_COMM_WORLD_SIZE"])
        pid = pid if pid is not None else int(env["OMPI_COMM_WORLD_RANK"])
        if coord is None and nprocs and nprocs > 1:
            raise RuntimeError(
                "MPI launch detected but no coordinator address; set MASTER_ADDR/"
                "MASTER_PORT (or COORDINATOR_ADDRESS) to a host:port on rank 0")
    # PMI convention (MPICH / Intel MPI / MVAPICH launchers export PMI_RANK)
    if auto_mpi_discovery and nprocs is None and "PMI_SIZE" in env:
        nprocs = int(env["PMI_SIZE"])
        pid = pid if pid is not None else int(env.get("PMI_RANK", 0))
        if coord is None and nprocs > 1:
            raise RuntimeError(
                "PMI launch detected but no coordinator address; set "
                "MASTER_ADDR/MASTER_PORT to a host:port on rank 0")
    # SLURM srun convention (reference: SlurmRunner relies on srun's env).
    # Gated on SLURM_STEP_ID — set only for srun-launched steps — so a bare
    # `python train.py` inside an sbatch allocation (which still exports
    # SLURM_NTASKS) is NOT mistaken for a distributed launch and left to
    # initialize single-process.
    if auto_mpi_discovery and nprocs is None and "SLURM_NTASKS" in env \
            and "SLURM_STEP_ID" in env:
        nprocs = int(env["SLURM_NTASKS"])
        pid = pid if pid is not None else int(env.get("SLURM_PROCID", 0))
        if coord is None and nprocs > 1:
            # first host of the allocation is the conventional coordinator
            nodelist = env.get("SLURM_JOB_NODELIST") or env.get("SLURM_NODELIST")
            if nodelist and "[" not in nodelist:
                coord = f"{nodelist.split(',')[0]}:{_DEFAULT_SLURM_PORT}"
            else:
                raise RuntimeError(
                    "SLURM launch detected but no coordinator address and "
                    "the nodelist is compressed; set MASTER_ADDR/MASTER_PORT "
                    "(or COORDINATOR_ADDRESS)")

    if coord is None or not nprocs or nprocs <= 1:
        _INITIALIZED = True
        return False

    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs,
                               process_id=pid)
    _INITIALIZED = True
    return True


def _int_env(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def is_initialized() -> bool:
    return _INITIALIZED


def get_world_size() -> int:
    """Number of participating *processes* (controllers).

    Note the semantic shift from the reference: torch launches one process per
    device, so its world_size == device count. JAX is single-controller per host;
    the SPMD width (device count) lives on the topology
    (``MeshTopology.world_size()``) / :func:`get_device_count`. Rank and
    world_size here are consistently process-level.
    """
    return jax.process_count()


def get_rank() -> int:
    """This process's rank in [0, get_world_size())."""
    return jax.process_index()


def get_device_count() -> int:
    """Global number of devices across all processes (reference's world_size)."""
    from ..accelerator import get_accelerator

    return get_accelerator().device_count()


def get_local_rank() -> int:
    """Rank within this host (reference: LOCAL_RANK env set per-process by
    ``launcher/launch.py``). JAX is one process per host, so this is the
    launcher-provided LOCAL_RANK when present, else 0."""
    v = os.environ.get("LOCAL_RANK")
    return int(v) if v is not None else 0


def barrier():
    """Host-level barrier (reference: ``comm.barrier``, ``comm/comm.py:411``).

    Under a single controller this drains async dispatch; under multi-controller it
    performs a tiny psum across all devices, which cannot complete until every
    process has joined.
    """
    if jax.process_count() == 1:
        jax.effects_barrier()
        return
    x = jnp.ones((jax.local_device_count(),))
    jax.block_until_ready(
        jax.pmap(lambda v: lax.psum(v, "i"), axis_name="i")(x))


# ======================================================================
# Reference-surface parity: root-based ops, p2p, coalesced variants and
# torch-compat aliases (reference comm/comm.py public API). Under SPMD
# every rank executes the same program, so "root" semantics become
# value-selection: non-root ranks get a defined value (documented per op)
# instead of being bystanders.
# ======================================================================
def reduce(x, axis_name, dst: int = 0):
    """Sum-reduce onto ``dst`` (reference ``comm.reduce``): the reduced
    value lands on rank ``dst``; every other rank keeps its input (the
    closest SPMD analog of torch's in-place root semantics)."""
    total = all_reduce(x, axis_name)   # honors the ALL_REDUCE kill switch
    return jnp.where(lax.axis_index(axis_name) == dst, total, x)


def gather(x, axis_name, dst: int = 0):
    """Gather shards onto ``dst`` (reference ``comm.gather``). SPMD has no
    bystanders, so EVERY rank receives the stacked [world, ...] result —
    rank ``dst`` reads it, others may ignore it (XLA DCEs unused outputs).
    """
    del dst  # root semantics dissolve under SPMD; kept for API parity
    return all_gather(x, axis_name, tiled=False)  # stacked [world, ...]


def scatter(x, axis_name, src: int = 0):
    """Scatter rank ``src``'s leading-dim shards (reference
    ``comm.scatter``): input [world, ...] on ``src``; every rank returns
    its own [...] shard."""
    full = broadcast(x, axis_name, src=src)  # broadcast logs the transfer
    n = lax.axis_size(axis_name)
    if full.shape[0] != n:
        # dynamic_index_in_dim would CLAMP a short leading dim, silently
        # delivering the wrong shard — reject like the reference does for a
        # wrong-length scatter_list
        raise ValueError(f"scatter input leading dim {full.shape[0]} != "
                         f"axis size {n}")
    return lax.dynamic_index_in_dim(full, lax.axis_index(axis_name), 0,
                                    keepdims=False)


def p2p(x, src: int, dst: int, axis_name):
    """Point-to-point transfer (reference ``send``/``recv`` pair, one
    collective under SPMD): rank ``dst`` returns rank ``src``'s value,
    every other rank keeps its own."""
    moved = ppermute(x, axis_name, [(src, dst)])  # honors the P2P switch
    return jnp.where(lax.axis_index(axis_name) == dst, moved, x)


def send(x, dst: int, axis_name, src: Optional[int] = None):
    """Reference ``comm.send``. SPMD is collective: the matching recv is
    the SAME call on the receiving rank, so ``send``/``recv`` both map to
    :func:`p2p`. ``src`` is REQUIRED — "the caller's rank" is not a
    static value under jit."""
    if src is None:
        raise ValueError("SPMD send needs the static source rank: "
                         "send(x, dst, axis, src=<rank>) — or use p2p()")
    return p2p(x, src, dst, axis_name)


def recv(x, src: int, axis_name, dst: Optional[int] = None):
    """Reference ``comm.recv`` — see :func:`send`."""
    if dst is None:
        raise ValueError("SPMD recv needs the static destination rank: "
                         "recv(x, src, axis, dst=<rank>) — or use p2p()")
    return p2p(x, src, dst, axis_name)


def all_reduce_coalesced(tensors, axis_name):
    """Reference ``all_reduce_coalesced``: one call over a list/pytree.
    XLA fuses the resulting psums, which is exactly what torch's
    coalescing manager buys."""
    return jax.tree_util.tree_map(lambda t: all_reduce(t, axis_name),
                                  tensors)


def all_gather_coalesced(tensors, axis_name):
    """Reference ``all_gather_coalesced`` over a list/pytree."""
    return jax.tree_util.tree_map(lambda t: all_gather(t, axis_name),
                                  tensors)


# ----- torch-compat aliases (reference keeps both spellings alive) -----
def all_gather_into_tensor(x, axis_name):
    """Reference ``all_gather_into_tensor`` (tensor-form all_gather)."""
    return all_gather(x, axis_name)


def reduce_scatter_tensor(x, axis_name):
    """Reference ``reduce_scatter_tensor`` (tensor-form reduce_scatter)."""
    return reduce_scatter(x, axis_name)


def all_to_all_single(x, axis_name, split_axis: int = 0,
                      concat_axis: int = 0, **kw):
    """Reference ``all_to_all_single`` (torch splits/concats dim 0
    implicitly — same defaults here)."""
    return all_to_all(x, axis_name, split_axis=split_axis,
                      concat_axis=concat_axis, **kw)


def inference_all_reduce(x, axis_name):
    """Reference ``inference_all_reduce`` (the op-builder fast path; XLA's
    psum IS the fast path here)."""
    return all_reduce(x, axis_name)


def monitored_barrier(timeout=None):
    """Reference ``monitored_barrier`` — barrier + log (straggler
    attribution needs no special path when XLA collectives deadlock
    loudly)."""
    del timeout
    _log("monitored_barrier", "world", jnp.zeros(()))
    barrier()


def get_global_rank(group=None, group_rank: int = 0) -> int:
    """Reference ``get_global_rank``: resolve a group-relative rank for a
    :func:`new_group` rank list (``None`` = the world group, where group
    rank == global rank). Mesh-axis groups need mesh coordinates — raise
    rather than return a plausible-looking wrong rank."""
    if isinstance(group, _RankGroup):
        return group.ranks[group_rank]
    if group is None:
        return group_rank
    raise TypeError(
        f"get_global_rank needs a new_group() handle or None, got "
        f"{group!r} — for mesh axes, derive ranks from the topology mesh "
        f"coordinates instead")


def get_world_group():
    """Reference ``get_world_group``. Rank domain: DEVICE ranks — the same
    domain every collective src/dst in this module uses (a single
    controller drives all local devices, so process ranks would make the
    world group [0] while ranks 0..7 participate in collectives)."""
    return _RankGroup(tuple(range(get_device_count())))


def get_all_ranks_from_group(group=None):
    """Reference ``get_all_ranks_from_group``."""
    if group is None:
        group = get_world_group()
    return list(group.ranks)


class _RankGroup:
    """Lightweight process-group handle (reference ``new_group`` returns a
    torch ProcessGroup). Collectives over arbitrary rank subsets are a
    MESH property under SPMD — build a topology whose axis holds these
    ranks (``build_topology``) instead of passing the handle to a
    collective."""

    def __init__(self, ranks):
        self.ranks = tuple(int(r) for r in ranks)

    def size(self) -> int:
        return len(self.ranks)

    def __repr__(self):
        return f"_RankGroup(ranks={self.ranks})"


def new_group(ranks):
    """Reference ``comm.new_group``. Returns a rank-list handle for
    bookkeeping APIs (:func:`get_global_rank`,
    :func:`get_all_ranks_from_group`); express subset COLLECTIVES as mesh
    axes (see :class:`_RankGroup`)."""
    return _RankGroup(ranks)


def destroy_process_group(group=None):
    """Reference ``destroy_process_group`` — jax.distributed teardown for
    the world group, no-op for sub-groups."""
    global _INITIALIZED
    if group is None or isinstance(group, _RankGroup) and \
            len(group.ranks) == get_device_count():
        try:
            jax.distributed.shutdown()
        except Exception:  # single-controller / already down
            pass
        _INITIALIZED = False  # torch parity: is_initialized() goes False
