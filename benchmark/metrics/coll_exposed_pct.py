"""Share of the traced window in which a collective ran on device 0 and no
other operation did: communication that compute does not hide."""
from benchmark import trace


def read(obs):
    tr = obs["trace"]
    if tr is None:
        return None
    lo, hi = obs["trace_window"]
    return 100.0 * trace.exposed_s(tr, sorted(tr["devices"])[0], lo, hi) \
        / (hi - lo)
