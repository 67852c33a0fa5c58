"""Process start to the instant JAX has the chip: interpreter start, the
harness's imports, ``import jax`` and the TPU runtime's own start-up. A user
feels it before every ``setup_s``; it is kept apart because it moved by
8 s between runs of one call on one machine and nothing in the repo moves
it (PERF.md section 2)."""


def read(obs):
    split = obs.get("split")
    return split["start_s"] + split["runtime_s"] if split else None
