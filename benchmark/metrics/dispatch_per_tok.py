"""Host-scheduled device dispatches (``engine.host_dispatches``) per output
token delivered in the window: a count, it repeats exactly when the work
does."""
from benchmark import window


def read(obs):
    t0, t1 = obs["window"]
    rounds = [r for r in obs["rounds"] if t0 < r[1] <= t1]
    tokens = window.tokens_in_window(obs["requests"], t0, t1)
    if not rounds or not tokens:
        return None
    before = [r[3] for r in obs["rounds"] if r[1] <= t0]
    return (rounds[-1][3] - (before[-1] if before else 0)) / tokens
