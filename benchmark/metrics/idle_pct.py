"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals over the window, mean over chips)."""
from benchmark import trace


def read(obs):
    if obs["trace"] is None:
        return None
    lo, hi = obs["trace_window"]
    return 100.0 * (1.0 - trace.busy_s(obs["trace"], lo, hi) / (hi - lo))
