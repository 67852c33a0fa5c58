"""Device time of ONE pass of a looped stack's ``decode_forward``, median
over the traced window's executions, in milliseconds: the operations under
the program's ``loop_pass`` scope (the layer scan and the final norm that
closes the pass; found by instruction name, ``benchmark/scopes.py``) inside
each execution, over the passes it ran (``passes`` of the round's record).
Four of them, the exit and the head are the step.

``loop_exit_share_pct`` is this reader with ``what: "exit_share_pct"``:
the device time under the ``loop_exit`` scope (the gate's product over the
passes' kept rows, the exit distribution, the choice, the count) as a share
of the same executions' own time. Expected under 1 %: there to show if it
is not.

Nothing to read, and ``None``: a program without the scope (every model
whose layers run once; every commit before the one that added it), records
without ``passes``, no trace."""
from benchmark import scopes, spans, window

SCOPES = ("loop_pass", "loop_exit")


def pass_and_exit_seconds(obs, program="decode_forward"):
    """``[(seconds under loop_pass, under loop_exit, the execution's own,
    passes), ...]`` of the traced executions of ``program``, or None."""
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, SCOPES)
    if not rounds or not ops:
        return None
    dev = spans.Device(obs["trace"])
    out = []
    for d in rounds:
        if d["program"] != program or not d.get("passes"):
            continue
        ran = dev.forward(program, d["t0"], d["t1"])
        if not ran:
            continue
        inside = [(label, dur) for label, prog, start, dur in ops
                  if prog == program and ran[0] <= start < ran[1]]
        out.append((sum(s for label, s in inside if label == "loop_pass"),
                    sum(s for label, s in inside if label == "loop_exit"),
                    ran[1] - ran[0], d["passes"]))
    return out or None


def read(obs, what="pass_ms"):
    ran = pass_and_exit_seconds(obs)
    if not ran:
        return None
    if what == "exit_share_pct":
        return 100.0 * sum(e for _p, e, _t, _n in ran) \
            / sum(t for _p, _e, t, _n in ran)
    return 1e3 * window.percentile(
        [in_pass / passes for in_pass, _e, _t, passes in ran], 0.5)
