"""Share of the window's round time that belongs to ``ragged_forward``
rounds: the rounds that carried a prompt. A round returns while the forward
it launched still runs, and the NEXT round waits for it (``readback``), so a
record's ``[t0, t1]`` holds the forward of the record BEFORE it: each
record's time is charged to that record's ``program``, not to its own
(which would give the share of the rounds that LAUNCH a ragged forward, each
mostly the wait for whatever ran before). A record whose predecessor launched nothing, or was dropped by the session's
ring, is charged to no program and stays in the total.
``mixed_round_share_pct`` approximates the same share from outside."""
from benchmark import spans


def read(obs):
    records = spans.window_records(obs)
    if not records:
        return None
    before = {d["round"] + 1: d["program"] for d in spans.round_records(obs)}
    took = [(d["t1"] - d["t0"], before.get(d["round"])) for d in records]
    return 100.0 * sum(t for t, p in took if p == "ragged_forward") \
        / sum(t for t, _p in took)
