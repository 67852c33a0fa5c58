"""Median device time of one execution of a named program in the traced
window (``args: {"program": ...}``; the ``*_fwd_ms`` metrics alias this)."""
from benchmark import trace, window


def read(obs, program):
    tr = obs["trace"]
    if tr is None:
        return None
    times = trace.program_times(tr, sorted(tr["devices"])[0], program)
    return 1e3 * window.percentile(times, 0.5) if times else None
