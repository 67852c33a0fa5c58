"""Mean number of live sequences at the start of a round, over the window's
rounds (a closed loop at saturation should hold ``clients``)."""


def read(obs):
    t0, t1 = obs["window"]
    live = [r[2] for r in obs["rounds"] if t0 < r[1] <= t1]
    return sum(live) / len(live) if live else None
