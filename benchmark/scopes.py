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
    (or of its ``(name, op_name path)`` pairs, read before) whose ``op_name``
    path has one of ``labels`` as a component (the innermost, where scopes
    nest)."""
    out = {}
    pairs = _INSTRUCTION.findall(hlo_text) if isinstance(hlo_text, str) \
        else hlo_text
    for name, path in pairs:
        label = next((part for part in reversed(path.split("/"))
                      if part in labels), None)
        if label:
            out[name] = label
    return out


def compiled_programs(obs):
    """``engine.compiled_programs()``, asked ONCE a run and kept on ``obs``:
    the engine lowers and compiles every program it ran anew each time it is
    asked (traced, lowered and loaded from the cache: tens of seconds at a
    cell's size, which every reader that wanted a scope paid again until PR
    62; a traced run of ``sala-docs-sat`` read its entries for minutes)."""
    engine = obs["engine"]
    kept = obs.get("compiled_programs")
    if kept is None or kept[0] is not engine:
        kept = obs["compiled_programs"] = (engine, engine.compiled_programs())
    return kept[1]


def instruction_paths(obs):
    """``{program: [(instruction name, op_name path), ...]}`` of the compiled
    programs' texts, kept on ``obs`` beside them."""
    programs = compiled_programs(obs)
    kept = obs.get("instruction_paths")
    if kept is None or kept[0] is not programs:
        kept = obs["instruction_paths"] = (programs, {
            program: _INSTRUCTION.findall(compiled.as_text())
            for program, compiled in programs.items()})
    return kept[1]


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
        names = {program: instructions_under(pairs, labels)
                 for program, pairs in instruction_paths(obs).items()}

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
