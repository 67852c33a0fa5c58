"""Median over the traced rounds of the time the device sat idle at one
``end`` of a round (``args: {"end": "head" | "tail"}``): ``head`` from the
round's start ``t0`` to the first operation of its forward program, ``tail``
from that program's last operation to the round's end ``t1`` (the program's
``round`` record on the trace's clock; operations that ran in between, such
as the sampler, are taken off). A round that launched no forward is idle
from end to end and counts as all head. Where a round returns while its
forward runs the tail is 0 by construction, and no metric names it; a
program that waits for its own forward inside the round has one, and names
it by an alias."""
from benchmark import spans, window


def per_round(obs):
    """``[(head_s, tail_s), ...]`` of the traced rounds, or ``None``."""
    rounds = spans.traced_rounds(obs)
    if not rounds:
        return None
    dev = spans.Device(obs["trace"])
    out = []
    for d in rounds:
        lo, hi = d["t0"], d["t1"]
        ran = d["program"] and dev.forward(d["program"], lo, hi)
        if not ran:
            out.append((dev.idle_s(lo, hi), 0.0))
        else:
            out.append((dev.idle_s(lo, ran[0]), dev.idle_s(ran[1], hi)))
    return out


def read(obs, end):
    idle = per_round(obs)
    if not idle:
        return None
    return 1e3 * window.percentile(
        [pair[end == "tail"] for pair in idle], 0.5)
