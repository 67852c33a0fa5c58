"""How unevenly the router loads the experts: per layer, the busiest
expert's routed rows over the mean expert's, averaged over layers, from the
engine's device-resident ``moe_load`` counter over the whole run
(``engine.moe_stats()``; 1.0 = uniform). Seeded-noise weights route near
uniformly, which a trained router does not: this says how near.

``moe_stats()["load"]`` is ``[expert layers, experts]`` over the router's
WHOLE width, whatever part of the experts the chip holds: routing is over
all of them. So the counter is held to its invariant as it stands: every
layer routed every live token ``num_experts_per_tok`` times and no pad row.
Where it does not hold the metric is left out and stderr says by how much.

A program that holds a share of the experts also gives ``"held"``, the
columns of ``load`` that are its own; the maximum and the mean are then over
those columns: the busiest expert HERE sets this chip's time."""
import sys


def read(obs):
    stats = getattr(obs.get("engine"), "moe_stats", lambda: None)()
    if not stats:
        return None
    load = stats["load"]
    want = obs["family"].arch(obs["config"])["num_experts_per_tok"] \
        * stats["live_tokens"]
    if want == 0 or (load.sum(1) != want).any():
        print(f"benchmark: expert_load_max_over_mean: rows routed per layer "
              f"{load.sum(1).tolist()} != num_experts_per_tok x live tokens "
              f"{want}: a pad row was routed or a live one was not",
              file=sys.stderr)
        return None
    if stats.get("held") is not None:
        load = load[:, stats["held"]]
    return float((load.max(1) / load.mean(1)).mean())
