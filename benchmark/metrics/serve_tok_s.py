"""Output tokens delivered inside the window, over the window: counted by
each token's own delivery time, not by finished requests."""
from benchmark import window


def read(obs):
    t0, t1 = obs["window"]
    return window.tokens_in_window(obs["requests"], t0, t1) / (t1 - t0)
