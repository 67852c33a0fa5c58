"""Share of the window's round time spent in rounds whose forward was
``ragged_forward`` (the ``program`` of the program's ``round`` record): the
rounds that carried a prompt, counted where they are dispatched.
``mixed_round_share_pct`` approximates the same share from outside."""
from benchmark import spans


def read(obs):
    records = spans.window_records(obs)
    if not records:
        return None
    took = [(d["t1"] - d["t0"], d["program"]) for d in records]
    return 100.0 * sum(t for t, p in took if p == "ragged_forward") \
        / sum(t for t, _p in took)
