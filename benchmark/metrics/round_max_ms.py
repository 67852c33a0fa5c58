"""The longest round of the window (the program's ``round`` record, ``t1 -
t0``): a machine that stalls for whole seconds shows here and nowhere in the
medians, which tells a stalled run from a slower program."""
from benchmark import spans


def read(obs):
    records = spans.window_records(obs)
    return 1e3 * max(d["t1"] - d["t0"] for d in records) if records else None
