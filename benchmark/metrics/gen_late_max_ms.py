"""How late the load generator sent its latest request (due time to send
time, over requests due in the window): a starved generator must not read as
a fast server."""
from benchmark import window

LAYER = "load generator"


def read(obs):
    late = window.late(obs["requests"], *obs["window"])
    return 1e3 * max(late) if late else None
