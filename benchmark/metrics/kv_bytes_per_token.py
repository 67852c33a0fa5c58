"""What one cached token costs the pool: the bytes of the engine's KV pool
arrays over their slots, all layers (``engine.kv``: K and V, or a latent
pool's one array). 7,680 B for six layers of one 640-wide bf16 row; 98,304 B
if the same 32 heads of 128 were cached as K and V. Counted from the
arrays' shapes: no transfer."""


def read(obs):
    kv = getattr(obs.get("engine"), "kv", None)
    if kv is None:
        return None
    pools = [p for p in (kv.k, getattr(kv, "v", None)) if p is not None]
    return sum(p.size * p.dtype.itemsize for p in pools) / kv.k.shape[1]
