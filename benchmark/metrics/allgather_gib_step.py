"""Bytes of parameter all-gather per step, from the program's collective
census of the compiled step (a count), in GiB."""


def read(obs):
    b = obs.get("param_gather_bytes")
    return None if b is None else b / 2**30
