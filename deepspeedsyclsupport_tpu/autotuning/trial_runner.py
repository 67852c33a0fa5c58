"""Subprocess trial executor for the autotuner.

Analog of the reference's experiment runner (``autotuning/scheduler.py``
launching each config as its own training job and scraping metric files):
one trial = one child interpreter, so an XLA OOM, a wedged compile, or a
crashing config kills the CHILD and scores -inf instead of taking down the
search. Payload in (JSON file path argv[1]), one JSON result line out.

Train trials measure engine.train_batch samples/sec on the framework model
zoo; serve trials measure v2-engine decode tokens/sec under the SplitFuse
scheduler — the two rungs the driver benches.
"""
import json
import sys
import time


def _train_trial(payload):
    import jax
    import numpy as np

    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model(payload["model"], **payload.get("model_kw", {}))
    engine, _, _, _ = dstpu.initialize(model=model, config=payload["config"])
    gbs = engine.train_batch_size()
    seq = int(payload.get("seq_len") or
              min(model.config.max_seq_len, 128))
    ids = jax.random.randint(jax.random.PRNGKey(0), (gbs, seq), 0,
                             model.config.vocab_size)
    batch = {"input_ids": ids}
    # at least one warmup step: it also compiles the program outside the
    # timed window
    for _ in range(max(1, int(payload.get("warmup", 1)))):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    steps = int(payload.get("steps", 3))
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    return {"throughput": steps * gbs / dt, "unit": "samples/s",
            "loss": float(np.asarray(jax.device_get(m["loss"])))}


def _serve_trial(payload):
    import jax
    import numpy as np

    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model(payload["model"], **payload.get("model_kw", {}))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params, config=payload["config"])
    rng = np.random.RandomState(0)
    n_seqs = int(payload.get("clients", 4))
    prompt_len = int(payload.get("prompt_len", 32))
    gen_len = int(payload.get("gen_len", 8))
    prompts = {u: rng.randint(1, model.config.vocab_size,
                              size=prompt_len).tolist()
               for u in range(n_seqs)}
    # warmup pass compiles prefill+decode in both KV states
    eng.warmup()
    out = eng.put(list(prompts), list(prompts.values()))
    # prefill is async-dispatched and logits are device-resident: force it
    # OUTSIDE the timed decode window or prefill cost pollutes the metric
    last = {u: int(np.argmax(np.asarray(out[u]))) for u in out}
    t0 = time.perf_counter()
    decoded = 0
    for _ in range(gen_len):
        res = eng.put(list(last), [[t] for t in last.values()])
        for u in list(last):
            if u in res:
                last[u] = int(np.argmax(res[u]))
                decoded += 1
    dt = time.perf_counter() - t0
    return {"throughput": decoded / dt, "unit": "tokens/s"}


def main() -> int:
    with open(sys.argv[1]) as f:
        payload = json.load(f)
    # persistent compile cache: sibling trials re-lower mostly identical
    # programs; sharing the cache makes a sweep compile-bound only once
    from ..utils.jax_cache import place_compile_cache

    place_compile_cache()
    try:
        result = (_serve_trial(payload) if payload.get("kind") == "serve"
                  else _train_trial(payload))
        result["ok"] = True
    except Exception as e:  # scored -inf by the parent
        result = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
    print("DSTPU_TRIAL " + json.dumps(result), flush=True)
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
