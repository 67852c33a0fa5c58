"""Share of the window's rounds that launched a forward in which that
forward was launched AHEAD: dispatched before the tokens sampled from the
forward before it were read back, so that the device went from the sampler
straight into it while the host waited for the copy (the ``round`` record's
``ahead``, which the session writes when its ``put`` dispatched with the
sampler's output still unread). Every per-token round of a session under
load does, save the first after idle, which has nothing to read: 99-100 in
a saturated closed loop. A round that launched nothing (the last tokens of
the last streams) is not counted.

Nothing to read, and ``None``: a program whose records lack the field (every
commit before the one that launches ahead reads the tokens first, always),
and a window in which no round launched."""
from benchmark import spans


def read(obs):
    launched = [d for d in spans.window_records(obs) or () if d["program"]]
    if not launched or not all("ahead" in d for d in launched):
        return None
    return 100.0 * sum(d["ahead"] for d in launched) / len(launched)
