"""90th percentile of the wait from a request's due time to its activation
(the session's ``queue_wait`` stage record plus the generator's lateness),
over requests due in the window."""
from benchmark import window


def read(obs, p=0.9):
    t0, t1 = obs["window"]
    queued = {s["data"]["uid"]: s["data"].get("dur", 0.0)
              for s in obs["stages"] if s["name"] == "serve/stage"
              and s["data"].get("stage") == "queue_wait"}
    waits = [r["sent"] - r["due"] + queued[r["uid"]] for r in obs["requests"]
             if t0 <= r["due"] < t1 and r["uid"] in queued]
    return 1e3 * window.percentile(waits, p) if waits else None
