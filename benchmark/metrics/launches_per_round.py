"""Program launches the host made per scheduling round: the trace's
``PJRT_LoadedExecutable_Execute`` events that start inside the program's own
round spans (``round`` records laid on the trace's clock), over the traced
rounds: the forward, the one program that gathers by slot and samples, and
whatever else the host launches between a round's ends (a program that
slices, stacks and converts per sequence shows here as some hundred)."""
import bisect

from benchmark import spans, trace


def read(obs):
    rounds = spans.traced_rounds(obs)
    if not rounds:
        return None
    starts = sorted(e[1] for e in obs["trace"]["host"]
                    if e[0] == trace.LAUNCH)
    return sum(bisect.bisect_right(starts, d["t1"])
               - bisect.bisect_left(starts, d["t0"])
               for d in rounds) / len(rounds)
