"""The ``serve`` path: ``InferenceEngineV2`` -> ``warmup()`` ->
``ServingSession`` under a closed or an open loop, in ONE thread.

The load generator and the server share the thread because the session's
``submit``/``step`` are one object's methods and not thread-safe: a request
that comes due during a round is sent when the round returns, and that
lateness is measured (``sent - due``), charged to the request's first-token
time (timed from ``due``) and reported.

Every output token is stamped with the harness's clock when ``step()``
returns it: that is when a client has it. All numbers of this path come from
those stamps, the harness's spans around ``step()``, the session's and the
engine's counters, and the device trace.
"""
import gc
import time

from . import reference, traffic, window

CLOCK = time.perf_counter


def build(cfg, family, seed, split):
    """Model, weights made on the device from ``seed`` in one jitted call,
    engine with its KV pool, and the engine's own warm-up (compile or cache
    load of the two forward programs). Fills ``split`` with seconds."""
    import jax

    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)
    from deepspeedsyclsupport_tpu.models import build_model

    t = CLOCK()
    model = build_model(cfg["preset"], **cfg.get("overrides", {}))
    check_widths(cfg, family, model.config)
    topology = dstpu.build_topology(dp=1, devices=jax.devices()[:1])
    params = seeded_params(model, seed, cfg["dtype"])
    jax.block_until_ready(params)
    split["weights_s"] = CLOCK() - t
    t = CLOCK()
    engine = InferenceEngineV2(model, params, topology=topology,
                               dtype=cfg["dtype"], seed=seed,
                               **cfg["engine"])
    jax.block_until_ready(engine.kv)
    split["pool_s"] = CLOCK() - t
    t = CLOCK()
    engine.warmup()
    split["compile_or_load_s"] = CLOCK() - t
    return model, engine


def seeded_params(model, seed, dtype=None, shardings=None):
    """The model's weights from ``seed`` in ONE jitted call, in ``dtype``
    (None: as initialised, float32), straight into ``shardings``. The seed
    enters as an ARGUMENT: baked into the program (``model.seed``) every new
    seed would be a new program and compile again, and set-up would depend
    on whether a seed had been seen."""
    import jax
    import jax.numpy as jnp

    def make(key):
        params = model.init_params(key)
        if dtype is None:
            return params
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def check_widths(cfg, family, model_config):
    """The program's preset must have the widths the configuration's file
    publishes: the file is the yardstick, the preset is under test. The
    family says which of the program's attributes hold them."""
    want = family.program_widths(cfg)
    got = {k: getattr(model_config, k) for k in want}
    if got != want:
        raise ValueError(f"{cfg['name']}: the program's preset "
                         f"{cfg['preset']!r} has {got}, the file says {want}")


class Loop:
    """One session under load. ``requests`` are the records
    ``benchmark.window`` reads; ``rounds`` one ``(t0, t1, live sequences,
    the engine's dispatch count so far)`` per ``step()``; ``idle_s`` the seconds slept for want of a request."""

    def __init__(self, engine, policy):
        from deepspeedsyclsupport_tpu.inference.v2.config import (
            ServingPolicyConfig)
        from deepspeedsyclsupport_tpu.inference.v2.serving import (
            ServingSession)

        self.engine = engine
        self.session = ServingSession(
            engine, ServingPolicyConfig(**policy), clock=CLOCK)
        self.requests, self.by_uid, self.rounds = [], {}, []
        self.shed = self.evicted = 0
        self.idle_s = 0.0

    def submit(self, req, due, client=None):
        now = CLOCK()
        rec = {"uid": len(self.requests), "due": due, "sent": now,
               "emits": [], "tokens": [], "budget": req["max_new_tokens"],
               "closed": None, "client": client, "prompt": req["tokens"],
               "evictions": 0}
        self.requests.append(rec)
        self.by_uid[rec["uid"]] = rec
        verdict = self.session.submit(rec["uid"], req["tokens"],
                                      req["max_new_tokens"], now=now)
        if verdict == "shed":
            rec["closed"] = "shed"
            self.shed += 1
        return rec

    def step(self):
        """One scheduling round; returns the records that finished."""
        import jax

        t0 = CLOCK()
        live = len(self.session.running)
        with jax.profiler.TraceAnnotation("bench/serve_step"):
            events = self.session.step()
        t1 = CLOCK()
        self.rounds.append((t0, t1, live, self.engine.host_dispatches))
        done = []
        for ev in events:
            rec = self.by_uid[ev.uid]
            if ev.kind == "token":
                rec["emits"] += [t1] * len(ev.tokens)
                rec["tokens"] += ev.tokens
            elif ev.kind == "finish":
                rec["closed"] = ev.reason
                done.append(rec)
            elif ev.kind == "shed":
                rec["closed"] = "shed"
                self.shed += 1
                done.append(rec)
            elif ev.kind == "evict":
                # KV pressure took the stream's blocks; under the
                # ``requeue`` policy it is prefilled again later and goes on
                rec["evictions"] += 1
                self.evicted += 1
        return done

    def sleep_until(self, t):
        import jax

        t0 = CLOCK()
        with jax.profiler.TraceAnnotation("bench/idle_no_request"):
            while True:
                left = t - CLOCK()
                if left <= 0:
                    break
                time.sleep(min(left, 0.002) if left < 0.004 else left - 0.002)
        self.idle_s += CLOCK() - t0

    def drain(self, limit_s=120.0):
        t_end = CLOCK() + limit_s
        while not self.session.idle:
            if CLOCK() > t_end:
                raise RuntimeError("the session never reached idle")
            self.step()


def staircase(loop, vocab, rng):
    """Warm what the engine's own ``warmup()`` leaves cold: the per-round
    host programs whose shapes follow the NUMBER of live sequences (the
    logits slice, the stack of drained rows, the sampler). One request
    arrives per round until ``max_sequences`` are live, each with a budget
    of ``max_sequences + 1`` tokens, so every count from 1 up and down again
    occurs once, in mixed rounds on the way up and pure decode on the way
    down — through ``submit``/``step`` only."""
    s_max = loop.engine.config.max_sequences
    for _ in range(s_max):
        loop.submit({"tokens": rng.integers(0, vocab, 8).tolist(),
                     "max_new_tokens": s_max + 1}, due=CLOCK())
        loop.step()
    loop.drain()


def run_closed(loop, mix, seed, vocab, seconds, hooks):
    """Closed loop of ``clients`` callers. The window opens at the return of
    the round in which the last client finished its first request (so
    finishes are spread over the rounds, not in lockstep), and closes at the
    return of the first round that ends ``seconds`` or more later: both ends
    lie on round boundaries, so a rate over it is all the work over all the
    time. A traced run keeps the same load going for ``hooks.tail_s``
    more seconds AFTER the window, under the profiler."""
    plan = traffic.ClosedPlan(mix, seed, vocab)
    free = [(c, CLOCK()) for c in range(plan.clients)]
    finished_once = set()
    t0 = t1 = t_stop = None
    while True:
        for client, since in free:
            loop.submit(plan.take(client), due=since, client=client)
        free = []
        done = loop.step()
        now = loop.rounds[-1][1]
        for rec in done:
            finished_once.add(rec["client"])
            free.append((rec["client"], now))
        if t0 is None:
            if len(finished_once) == plan.clients:
                t0 = now
                hooks.window_open()
        elif t1 is None:
            if now - t0 >= seconds:
                t1 = now
                hooks.window_close()
                if not hooks.tail_s:
                    break
                hooks.trace_start()
                t_stop = CLOCK() + hooks.tail_s
        elif now >= t_stop:
            hooks.trace_stop()
            break
        else:
            hooks.tick()
    loop.drain()
    return t0, t1


def run_open(loop, mix, seed, vocab, seconds, hooks):
    """Open loop at the mix's fixed rate: every arrival is sent at the first
    round boundary at or after its due time; the window is ``seconds`` long
    and opens when the ramp's arrivals (negative ``due``) are through. A
    traced run lays out ``hooks.tail_s`` more seconds of arrivals AFTER
    the window and takes its trace there."""
    arrivals = traffic.open_arrivals(mix, seed, seconds, vocab,
                                     extra_seconds=hooks.tail_s)
    t0 = CLOCK() + 0.05 - arrivals[0]["due"]   # the first is due in 50 ms
    t1 = t0 + seconds
    i, phase = 0, "ramp"
    while i < len(arrivals) or not loop.session.idle:
        now = CLOCK()
        if phase == "ramp" and now >= t0:
            phase = "window"
            hooks.window_open()
        if phase == "window" and now >= t1:
            phase = "after"
            hooks.window_close()
            if hooks.tail_s:
                phase = "trace"
                hooks.trace_start()
        if phase == "trace":
            hooks.tick()
            if now >= t1 + hooks.tail_s:
                phase = "after"
                hooks.trace_stop()
        while i < len(arrivals) and t0 + arrivals[i]["due"] <= now:
            loop.submit(arrivals[i], due=t0 + arrivals[i]["due"])
            i += 1
        if loop.session.idle:
            if i < len(arrivals):
                loop.sleep_until(min(t0 + arrivals[i]["due"],
                                     t1 if phase == "window" else 1e30))
            continue
        loop.step()
    if phase == "window":
        hooks.window_close()
    if phase == "trace":
        hooks.trace_stop()
    return t0, t1


def probe_of(load, t0, t1, n_tokens=8):
    """The request the plain reference is held against, and how many of its
    tokens: a request that was evicted and taken up again, if the run had
    one, over its WHOLE output (what a second prefill of prompt + emitted
    tokens could get wrong comes after the eviction); else the shortest
    finished prompt due inside the window, over its first ``n_tokens``.
    ``(None, 0)`` where nothing finished."""
    done = [r for r in load if window.ok(r)]
    again = [r for r in done if r["evictions"]]
    if again:
        rec = min(again, key=lambda r: len(r["prompt"]))
        return rec, len(rec["tokens"])
    rec = min((r for r in done if t0 <= r["due"] < t1),
              key=lambda r: len(r["prompt"]), default=None)
    return rec, n_tokens if rec else 0


def reference_check(cfg, family, engine, rec, n_tokens=8, tol=0.1):
    """One finished request's first tokens against the plain reference's
    greedy choice: within ``tol`` logit standard deviations (a wrong token
    sits ~4 below the argmax; bf16 kernels may break a near-tie
    differently, never more)."""
    margins = reference.greedy_margins(
        family, cfg, engine.params, rec["prompt"], rec["tokens"][:n_tokens])
    return max(margins), max(margins) <= tol


def freeze_garbage():
    """Everything alive after warm-up is long-lived: move it out of the
    collector's sight so that the loop's own garbage is all a collection
    has to walk (the standard preparation of a serving process)."""
    gc.collect()
    gc.freeze()
