"""Collective census vs analytic expectation for a compiled train step.

EQuARX (PAPERS.md) frames collective-byte accounting as the metric that
decides compute-bound vs interconnect-bound at pod scale; ZeRO-Infinity's
bandwidth-centric design likewise lives on statically knowable transfer
volumes. Under JAX both are exact static analyses: the compiled step is one
HLO module, and every partitioner-inserted collective is a line in it
(``comm/hlo_comms.py`` does the parsing).

What can be *exactly* predicted and what can't:

* **param-gather** traffic (ZeRO-3 all-gather of fsdp-sharded params) is
  canonical — one full-bytes all-gather per sharded param per use (XLA CSEs
  the fwd/bwd pair when the gathered value stays live; remat re-gathers).
* **grad-sync** traffic is semantically fixed (every grad leaf must be
  summed across the batch-splitting axes) but its *lowering* is XLA's
  choice: all-reduce, reduce-scatter, or all-to-all + local reduce are all
  legal spellings of the same data movement. The census therefore CLASSIFIES
  observed collectives into traffic classes and checks class totals, not
  opcode-exact lists.
* anything unclassified is a **reshard suspect** — the resharding analyzer's
  input (``resharding.py``).
"""
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..comm.hlo_comms import parse_collectives

#: collectives ≤ this payload are scalar control sync (loss means, overflow
#: flags, grad-norm reductions) — never param/grad traffic
SCALAR_BYTES = 64


def _as_text(compiled_or_text: Any) -> str:
    if isinstance(compiled_or_text, str):
        return compiled_or_text
    return compiled_or_text.as_text()


def collective_census(compiled_or_text: Any) -> List[Dict[str, Any]]:
    """Every data-moving collective of a compiled step program:
    ``[{op, bytes, shape, group_size}]`` (see ``hlo_comms.parse_collectives``)."""
    return parse_collectives(_as_text(compiled_or_text))


# ---------------------------------------------------------------- expectation
@dataclass
class CollectiveExpectation:
    """Analytic per-step expectation derived from the parallelism config.

    Byte counts are HLO payload bytes (full logical result), matching the
    census; wire bytes per device are ``(N-1)/N`` of that for ring
    implementations — a constant factor that cancels in expected-vs-observed
    comparison.
    """
    param_gather_count: int          # sharded params × gathers_per_param
    param_gather_bytes: int          # Σ full bytes of fsdp-sharded params
    grad_sync_count: int             # grad leaves needing cross-batch sum
    grad_sync_bytes: int             # Σ full bytes of those grads
    group_size: int                  # devices in the batch-splitting group
    scalar_sync_max_bytes: int = 16 * SCALAR_BYTES
    notes: Dict[str, Any] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return self.param_gather_bytes + self.grad_sync_bytes


def _leaf_entries(tree: Any, shardings: Any = None
                  ) -> List[Tuple[int, bool, Tuple[int, ...]]]:
    """[(full_bytes, fsdp_sharded, shape)] per array leaf of ``tree``."""
    import jax
    from jax.sharding import NamedSharding

    leaves = jax.tree_util.tree_leaves(tree)
    s_leaves = (jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
        if shardings is not None else [None] * len(leaves))
    out = []
    for leaf, s in zip(leaves, s_leaves):
        shape = np.shape(leaf)
        if not shape:
            continue  # scalars sync in the scalar class
        dt = np.dtype(getattr(leaf, "dtype", np.float32))
        nbytes = int(math.prod(shape)) * dt.itemsize
        s = s if s is not None else getattr(leaf, "sharding", None)
        spec = getattr(s, "spec", None) or ()
        axes = {a for e in spec for a in
                ((e,) if not isinstance(e, tuple) else e) if a}
        out.append((nbytes, "fsdp" in axes, tuple(shape)))
    return out


def expected_train_collectives(params: Any, topo: Any, stage: int,
                               param_shardings: Any = None,
                               grad_shardings: Any = None,
                               gathers_per_param: int = 1,
                               ) -> CollectiveExpectation:
    """Canonical per-step expectation for the engine's fused train step.

    * stage 3: each fsdp-sharded param is all-gathered ``gathers_per_param``
      times (1 when XLA keeps the gathered value live across fwd/bwd, 2
      under remat); every grad leaf is summed across (data, fsdp).
    * stage 0-2: params replicated (no gather class); every grad leaf is
      summed across the batch-splitting axes.

    ``gradient_accumulation_steps`` does not multiply anything: the scan
    accumulates *locally* and the engine syncs once per optimizer step.
    """
    entries = _leaf_entries(params, param_shardings)
    grad_entries = (_leaf_entries(params, grad_shardings)
                    if grad_shardings is not None else entries)
    sharded = [e for e in entries if e[1]] if stage >= 3 else []
    axes = topo.axis_sizes
    group = axes.get("data", 1) * axes.get("fsdp", 1)
    # a group of 1 moves no bytes: XLA emits no collective for a
    # single-member axis, so the expectation must be zero or the
    # conservation check flags a correct single-device program
    if axes.get("fsdp", 1) == 1:
        sharded = []
    if group == 1:
        grad_entries = []
    return CollectiveExpectation(
        param_gather_count=len(sharded) * gathers_per_param,
        param_gather_bytes=sum(e[0] for e in sharded) * gathers_per_param,
        grad_sync_count=len(grad_entries),
        grad_sync_bytes=sum(e[0] for e in grad_entries),
        group_size=group,
        notes={"stage": stage, "gathers_per_param": gathers_per_param,
               "n_param_leaves": len(entries),
               "n_sharded_params": len(sharded)})


# ------------------------------------------------------------- classification
@dataclass
class CollectiveClasses:
    """Observed census split into traffic classes."""
    param_gather: List[Dict[str, Any]] = field(default_factory=list)
    grad_sync: List[Dict[str, Any]] = field(default_factory=list)
    scalar_sync: List[Dict[str, Any]] = field(default_factory=list)
    other: List[Dict[str, Any]] = field(default_factory=list)

    def bytes_of(self, cls: str) -> int:
        """Bytes the class moves in ONE run of the module."""
        return sum(e["bytes"] * e.get("executions", 1)
                   for e in getattr(self, cls))

    def counts(self) -> Dict[str, int]:
        return {c: len(getattr(self, c)) for c in
                ("param_gather", "grad_sync", "scalar_sync", "other")}

    def summary(self) -> Dict[str, Dict[str, int]]:
        return {c: {"count": len(getattr(self, c)),
                    "total_bytes": self.bytes_of(c)}
                for c in ("param_gather", "grad_sync", "scalar_sync", "other")}


GRAD_SYNC_OPS = ("all-reduce", "reduce-scatter")


def _parts(rec: Dict[str, Any]) -> List[Dict[str, int]]:
    """Array payloads of one census record (several for a combined op). A
    hand-written record without ``parts`` is one fp32 array."""
    return rec.get("parts") or [{"bytes": rec["bytes"],
                                 "elems": rec["bytes"] // 4}]


def _leaf_elems(tree: Any, shardings: Any = None
                ) -> Tuple[set, set]:
    """(element counts a collective over ANY param leaf can move, the same
    for fsdp-sharded leaves only). Counted in elements, not bytes, because
    the transport dtype is the compiler's business: a TPU step casts the
    fp32 master shards to bf16 BEFORE gathering them and sums bf16 grads,
    and the quantized collectives move int8. A leaf contributes its full
    size and — for a stacked ``[L, ...]`` leaf of a scanned trunk, whose
    collectives run per layer inside the loop — the size of one slice along
    its leading dim."""
    every, sharded = set(), set()
    for _, is_sharded, shape in _leaf_entries(tree, shardings):
        sizes = {math.prod(shape)}
        if len(shape) >= 2:
            sizes.add(math.prod(shape[1:]))
        every |= sizes
        if is_sharded:
            sharded |= sizes
    return every, sharded


def classify_collectives(census: Sequence[Dict[str, Any]],
                         params: Any,
                         param_shardings: Any = None,
                         ) -> CollectiveClasses:
    """Attribute each observed collective to a traffic class by matching
    the ELEMENT COUNT of what it moves against the param tree
    (:func:`_leaf_elems` — full leaves and per-layer slices, any dtype):

    * ``param_gather`` — an all-gather of a sharded param;
    * ``grad_sync`` — an all-reduce/reduce-scatter of a param-shaped array
      (grads are param-shaped), or the all-to-all of a ONE-byte-per-element
      transport (ZeRO++ qgZ int8 quant-reduce, ``comm/quantized.py``);
    * ``scalar_sync`` — payload ≤ ``SCALAR_BYTES`` (loss/overflow/norm);
    * ``other`` — everything else: quantization scale sidecars, padded or
      exotic grad-sync lowerings and genuine resharding traffic. A canonical
      layout leaves this class empty; growth here is the resharding signal.

    A COMBINED op (XLA's combiner merges several leaves' syncs into one
    tuple-result collective — jax 0.9 lowers the whole ZeRO-3 grad sync
    that way) belongs to a class when EVERY element of its result tuple
    does. Class totals are bytes per run of the module (an op in a layer
    scan's body counts once per trip), never op counts.
    """
    every, sharded = _leaf_elems(params, param_shardings)
    out = CollectiveClasses()
    for rec in census:
        elems = [p["elems"] for p in _parts(rec)]
        if rec["bytes"] <= SCALAR_BYTES:
            out.scalar_sync.append(rec)
        elif rec["op"] == "all-gather" and all(e in sharded for e in elems):
            out.param_gather.append(rec)
        elif rec["op"] in GRAD_SYNC_OPS and all(e in every for e in elems):
            out.grad_sync.append(rec)
        elif rec["op"] == "all-to-all" and rec["bytes"] == sum(elems) \
                and all(e in every for e in elems):
            out.grad_sync.append(rec)
        else:
            out.other.append(rec)
    return out


# -------------------------------------------------------------------- checker
@dataclass
class CollectiveCheck:
    ok: bool
    classes: CollectiveClasses
    expectation: CollectiveExpectation
    problems: List[str] = field(default_factory=list)

    def report(self) -> str:
        lines = [f"collective census check: {'OK' if self.ok else 'FAIL'}"]
        exp = self.expectation
        s = self.classes.summary()
        lines.append(f"  param_gather: observed {s['param_gather']['count']} "
                     f"ops / {s['param_gather']['total_bytes']} B, expected "
                     f"{exp.param_gather_count} / {exp.param_gather_bytes} B")
        lines.append(f"  grad_sync:    observed {s['grad_sync']['count']} "
                     f"ops / {s['grad_sync']['total_bytes']} B, expected "
                     f"{exp.grad_sync_count} / {exp.grad_sync_bytes} B")
        lines.append(f"  scalar_sync:  {s['scalar_sync']['count']} ops / "
                     f"{s['scalar_sync']['total_bytes']} B")
        lines.append(f"  other:        {s['other']['count']} ops / "
                     f"{s['other']['total_bytes']} B")
        lines.extend(f"  PROBLEM: {p}" for p in self.problems)
        return "\n".join(lines)


def check_collectives(census: Sequence[Dict[str, Any]],
                      expectation: CollectiveExpectation,
                      params: Any,
                      param_shardings: Any = None,
                      exact: bool = True,
                      other_budget_bytes: int = 0) -> CollectiveCheck:
    """Compare an observed census against the analytic expectation.

    ``exact=True`` (canonical layouts) demands class totals equal the
    expectation and the ``other`` class stay within ``other_budget_bytes``.
    ``exact=False`` only enforces the conservation law — total observed
    param+grad class bytes never *exceeds* the expectation (more traffic
    than the analytic model means an unintended gather/sync) and grad sync
    is not silently missing when the expectation requires it.
    """
    classes = classify_collectives(census, params, param_shardings)
    problems: List[str] = []
    pg_bytes, gs_bytes = classes.bytes_of("param_gather"), classes.bytes_of("grad_sync")
    if exact:
        n_gathered = sum(len(_parts(r)) for r in classes.param_gather)
        if n_gathered != expectation.param_gather_count:
            problems.append(
                f"param_gather payloads {n_gathered} != expected "
                f"{expectation.param_gather_count}")
        if pg_bytes != expectation.param_gather_bytes:
            problems.append(f"param_gather bytes {pg_bytes} != expected "
                            f"{expectation.param_gather_bytes}")
        if gs_bytes != expectation.grad_sync_bytes:
            problems.append(f"grad_sync bytes {gs_bytes} != expected "
                            f"{expectation.grad_sync_bytes}")
        if classes.bytes_of("other") > other_budget_bytes:
            problems.append(
                f"unclassified collective traffic {classes.bytes_of('other')} B "
                f"exceeds budget {other_budget_bytes} B (resharding suspect — "
                f"see resharding_audit)")
    else:
        if pg_bytes > expectation.param_gather_bytes:
            problems.append(f"param_gather bytes {pg_bytes} exceed analytic "
                            f"budget {expectation.param_gather_bytes}")
        if expectation.grad_sync_bytes and not (
                gs_bytes or classes.other):
            problems.append("no grad-sync traffic observed but the config "
                            "requires cross-batch gradient summation")
    scalar = classes.bytes_of("scalar_sync")
    if scalar > expectation.scalar_sync_max_bytes:
        problems.append(f"scalar sync {scalar} B exceeds "
                        f"{expectation.scalar_sync_max_bytes} B — a tensor is "
                        f"hiding in the scalar class or control sync grew")
    groups = {e.get("group_size") for e in census if e.get("group_size")}
    bad_groups = groups - {expectation.group_size, None}
    if bad_groups and exact:
        problems.append(f"collectives over unexpected group sizes "
                        f"{sorted(bad_groups)} (expected "
                        f"{expectation.group_size})")
    return CollectiveCheck(ok=not problems, classes=classes,
                           expectation=expectation, problems=problems)
