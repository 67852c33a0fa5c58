"""Median over the window's rounds of the host time a round spent in a group
of its phases (``args: {"group": ...}``, ``spans.GROUPS``), from the
program's own ``round`` records (``phases`` = seconds by
``dstpu/serve/<phase>`` span, which partition the round). The four groups of
one round add up to its ``t1 - t0``; the medians of a window that mixes
80 ms decode rounds with 150 ms prompt rounds need not. Time on the host's
clock, NOT host work alone: a launch blocks while the device's launch queue
is full, so ``post`` (the ``collect`` slices behind the forward) and, where
the forward ends in the next round, ``pre`` hold mostly the forward's own
time. What the host costs the chip is ``round_idle_head_ms`` and
``launches_per_round``."""
from benchmark import spans, window


def read(obs, group):
    records = spans.window_records(obs)
    if not records:
        return None
    return 1e3 * window.percentile(
        [sum(d["phases"].get(p, 0.0) for p in spans.GROUPS[group])
         for d in records], 0.5)
