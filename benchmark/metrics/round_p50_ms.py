"""Median wall time of one ``session.step()`` (the harness's span around
it), over the rounds that ended inside the window."""
from benchmark import window


def read(obs):
    t0, t1 = obs["window"]
    rounds = [r[1] - r[0] for r in obs["rounds"] if t0 < r[1] <= t1]
    return 1e3 * window.percentile(rounds, 0.5) if rounds else None
