"""What one live sequence costs the recurrent-state pool, whatever its
context: the bytes of one slot over all the Mamba layers, the SSM state in
its dtype and the convolution's tail (``engine.state_stats()``). 25.6 MB for
twelve layers of [64 heads, 64, 128] float32 + [3, 6144] bf16, against 3 KB
a TOKEN of keys and values. Counted from the arrays' shapes: no transfer.

Nothing to read, and ``None``: an engine without ``state_stats()`` (every
commit before the one that added it) or a model without such state."""


def read(obs):
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    return stats["bytes_per_slot"] if stats else None
