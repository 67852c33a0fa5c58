"""Device milliseconds of ONE training step by region and by pass: the median,
over the executions of ``train_batch_fn`` that lie wholly in the traced
window, of the step's busy time that belongs to the asked regions
(``args: {"region": ...}``: ``embed attn mlp head loss optimizer other``, one
or a list) or to the asked passes of the model regions (``args: {"passes":
[...]}``: ``fwd bwd recompute``).

The program says which region and pass each of its instructions belongs to:
``engine.compiled_train_step()`` hands the compiled step to
``monitor/mfu.py``, whose ``published("train_batch_fn")`` reads them off the
``op_name`` path of every instruction's line (the ``mfu.<region>`` label; a
``rematted_computation`` or ``transpose(`` component). The device trace names
an operation by its instruction, so every instant of a step's busy time goes
to exactly one owner: the leaf operation running then (the latest started,
where several run), looked up by ``trace.op_name``; a Pallas kernel is an
instruction like any other. A collective owns an instant only where it runs
alone, which is what ``coll_exposed_pct`` reads: a gather that compute hides
costs its region nothing. So in every step

    embed + attn + mlp + head + loss + optimizer + other + collective
    fwd + bwd + recompute      + optimizer + other + collective

are each the step's busy time (``other``: instructions under no region;
``collective``: ``split(obs)`` has it, no metric reads it), less what the
map does not know (``unmapped``: a stale map, and a sum that falls short).
Milliseconds a step and not shares: a share falls when a neighbour grows.

``None`` without a trace or without a published map (a program from before
``mfu.published``, and a run that never called ``compiled_train_step()``).
"""
import bisect
import collections

from benchmark import trace, window

PROGRAM = "train_batch_fn"
# the regions whose time has a pass (the benchmark's own copy of
# ``mfu.MODEL_REGIONS``: the parent's ``monitor/mfu.py`` has none, and what a
# metric counts stays with the benchmark)
MODEL_REGIONS = ("embed", "attn", "mlp", "head", "loss")


def published_map():
    """``{instruction: {"region", "pass", ...}}`` of the step the program
    last compiled, or ``None`` where it publishes none."""
    from deepspeedsyclsupport_tpu.monitor import mfu

    ask = getattr(mfu, "published", None)
    return ask(PROGRAM) if ask else None


def steps_of(tr, plane, lo, hi):
    """The leaf operations ``[(text, start, seconds), ...]`` of each
    execution of the step that lies wholly inside ``[lo, hi]``."""
    names = trace.program_names(tr, plane)
    runs = sorted((m[1], m[1] + m[2]) for m in tr["devices"][plane]["modules"]
                  if names[m[0]] == PROGRAM and lo <= m[1]
                  and m[1] + m[2] <= hi)
    starts = [r[0] for r in runs]
    steps = [[] for _ in runs]
    for program, text, start, dur in trace.ops_by_program(tr, plane):
        k = bisect.bisect_right(starts, start) - 1
        if program == PROGRAM and k >= 0 and start <= runs[k][1]:
            steps[k].append((text, start, dur))
    return steps


def owner_of(text, opmap):
    """``(region, pass)`` an operation's time goes to."""
    if trace.op_kind(text) == "collective":
        return ("collective", None)
    entry = opmap.get(trace.op_name(text))
    return (entry["region"], entry.get("pass")) if entry \
        else ("unmapped", None)


def split_step(ops, opmap):
    """``{(region, pass): seconds}`` of one step: its busy time, every
    instant given to one owner."""
    ops = [op for op in ops if op[2] > 0]     # an instant owns no time
    owners = [owner_of(text, opmap) for text, _s, _d in ops]
    # at one time, ends (0) before starts (1)
    points = sorted([(s, 1, i) for i, (_t, s, _d) in enumerate(ops)]
                    + [(s + d, 0, i) for i, (_t, s, d) in enumerate(ops)])
    out, running, prev = collections.Counter(), set(), None
    for t, opening, i in points:
        if running and t > prev:
            compute = [j for j in running if owners[j][0] != "collective"]
            out[owners[max(compute or running, key=lambda j: ops[j][1])]] \
                += t - prev
        prev = t
        if opening:
            running.add(i)
        else:
            running.discard(i)
    return out


def split(obs):
    """``[{(region, pass): seconds}, ...]``, one per whole step of the traced
    window on device 0; ``None`` without a trace or a published map. Kept on
    ``obs``: nine metrics read it."""
    if "train_step_split" not in obs:
        tr = obs.get("trace")
        opmap = published_map() if tr is not None else None
        obs["train_step_split"] = None if opmap is None else [
            split_step(ops, opmap) for ops in steps_of(
                tr, sorted(tr["devices"])[0], *obs["trace_window"])]
    return obs["train_step_split"]


def read(obs, region=None, passes=None):
    steps = split(obs)
    if not steps:
        return None
    regions = (region,) if isinstance(region, str) else tuple(region or ())
    passes = tuple(passes or ())
    return 1e3 * window.percentile(
        [sum(s for (r, p), s in step.items()
             if r in regions or (r in MODEL_REGIONS and p in passes))
         for step in steps], 0.5)
