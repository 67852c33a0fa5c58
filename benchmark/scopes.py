"""The program's named scopes, laid on the device trace.

The device trace names an operation by its instruction (``%fusion.12 = ...``)
and carries no ``op_name`` (read off a v5e trace, PR 23); the compiled
program's own text carries both: every instruction's line ends in
``metadata={op_name="jit(decode_forward)/.../moe_experts/ragged_dot" ...}``,
the path of ``jax.named_scope`` labels it was traced under. So a reader that
wants one scope's device time takes the instruction names under that label
from ``engine.compiled_programs()`` and finds those names among the trace's
operations of the same program. A fusion carries its root's ``op_name``: an
elementwise step that XLA fused into a neighbour counts with the neighbour.

The TPU compiler replaces some operations by custom calls of its own and
gives them its own ``op_name``: ``jax.lax.ragged_dot`` becomes
``ragged-dot-metadata`` and ``ragged-dot-none[.n]`` (``tpu_custom_call``,
libtpu 0.0.34), outside every scope it was traced under. A reader names such
kernels by the prefix of their instruction name (``kernels``).

A program without the label (every commit before the one that added the
scope) has no such instruction: the readers get nothing and return ``None``.
"""
import re

from . import trace

# what jax.lax.ragged_dot compiles to on the TPU (libtpu 0.0.34), as
# ``scoped_ops``' ``kernels``: the grouped GEMMs of the ``moe_experts`` scope
RAGGED_DOT_KERNELS = (("ragged-dot", "moe_experts"),)

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M)


def instructions_under(hlo_text, labels):
    """``{instruction name: label}`` for every instruction of ``hlo_text``
    whose ``op_name`` path has one of ``labels`` as a component (the
    innermost, where scopes nest)."""
    out = {}
    for name, path in _INSTRUCTION.findall(hlo_text):
        label = next((part for part in reversed(path.split("/"))
                      if part in labels), None)
        if label:
            out[name] = label
    return out


def scoped_ops(obs, labels, kernels=()):
    """``[(label, program, start, seconds), ...]`` of the trace's leaf device
    operations that belong to one of ``labels``: by the compiled text, or as
    a custom call whose instruction name starts with a prefix in ``kernels``
    (``((prefix, label), ...)``). ``None`` without a trace or an engine. Kept
    on ``obs`` by what was asked for: compiling the programs' text again for
    every reader would cost seconds each."""
    tr, engine = obs.get("trace"), obs.get("engine")
    if tr is None or engine is None:
        return None
    key = ("scoped_ops", tuple(labels), tuple(kernels))
    if key not in obs:
        names = {program: instructions_under(compiled.as_text(), labels)
                 for program, compiled in engine.compiled_programs().items()}

        def label_of(program, text):
            name = trace.op_name(text)
            if trace.op_kind(text) == "kernel":
                for prefix, label in kernels:
                    if name.startswith(prefix) and label in labels:
                        return label
            return names.get(program, {}).get(name)

        plane = sorted(tr["devices"])[0]
        obs[key] = [(label, program, start, dur)
                    for program, text, start, dur
                    in trace.ops_by_program(tr, plane)
                    for label in (label_of(program, text),) if label]
    return obs[key]
