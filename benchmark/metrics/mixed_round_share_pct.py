"""Share of the window's time spent in rounds that carried a prompt: rounds
during which some request already sent was still waiting for its first
token (its prompt, or a chunk of it, shares the round's forward with the
decoding sequences, or it waits behind one that does). Those rounds are the
long gaps of a decoding stream, so they set ``itl_p99_ms``; the rest are
pure decode. From the harness's own round spans and token stamps."""


def read(obs):
    t0, t1 = obs["window"]
    rounds = [r for r in obs["rounds"] if t0 < r[1] <= t1]
    if not rounds:
        return None
    waits = [(r["sent"], r["emits"][0] if r["emits"] else float("inf"))
             for r in obs["requests"] if r["closed"] != "shed"]
    mixed = sum(end - start for start, end, *_ in rounds
                if any(sent <= start and first >= end
                       for sent, first in waits))
    return 100.0 * mixed / sum(r[1] - r[0] for r in rounds)
