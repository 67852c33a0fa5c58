"""Trained tokens over the window: all its whole steps over all its time,
between two ``block_until_ready``."""


def read(obs):
    t0, t1 = obs["window"]
    return obs["steps"] * obs["tokens_per_step"] / (t1 - t0)
