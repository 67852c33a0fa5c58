"""Benchmark harness — rungs run cheapest-first, one JSON line per success.

Rungs (each an isolated child process so a hang/OOM in one cannot eat the
others' window):
  probe    — which platform answers; the parent exits non-zero unless it
             is a TPU
  kernels  — COMPILED (non-interpret) Pallas parity + throughput microbench:
             flash fwd / fwd+bwd, ragged paged prefill, paged decode, each
             against its jnp oracle (TPU only — interpret numbers are not
             kernel evidence)
  train    — the training-MFU ladder on the flagship Llama-family model
  serve    — FastGen-style serving benchmark on the v2 ragged engine:
             closed-loop clients, p50/p95 TTFT, decode tokens/sec/chip, and
             a SplitFuse-on/off A-B (reference headline: 2.3x effective
             throughput, ``blogs/deepspeed-fastgen/README.md:28,139``)

The FINAL line aggregates every rung result under ``detail.rungs`` so a
parser that keeps only the last JSON line still sees everything.
``vs_baseline`` semantics per rung are in each line's ``detail.baseline``.

Contract: the parent never imports jax (a chip belongs to ONE process — each
rung child holds it in turn, and the probe child has exited before the first
rung starts). When the probe finds no TPU the parent exits non-zero: there
is no CPU plan and no rung falls back to the CPU. A rung that fails or hangs
is recorded under ``detail.rung_errors`` and makes the exit code non-zero;
the platform is recorded in every line.
"""
import json
import os
import subprocess
import sys
import time

# bf16 peak FLOPs and HBM bandwidth by platform (per chip): TPU v5e. A
# platform that is not in the table has no peak — a CPU run reports none.
PEAKS = {"tpu": 197e12}
HBM_GBPS = {"tpu": 819.0}
REFERENCE_MFU = 0.54       # Ulysses 175/312 TFLOPs on A100 (BASELINE.md)
REFERENCE_FASTGEN_SPEEDUP = 2.3  # FastGen effective-throughput headline
RUNG_ENV = "DSTPU_BENCH_RUNG"


def _emit(result):
    print(json.dumps(result), flush=True)


class _ScenarioTimeout(RuntimeError):
    """A single scenario (one load point / A-B arm) overran its budget.
    Raised from inside the driving loop so the caller can flush whatever
    the sweep completed so far instead of losing the whole rung (the r05
    rc=124 failure mode: the bench died with everything buffered)."""


def _attn_overrides(attn):
    """Serving-config overrides for an explicit attention impl (the XLA
    fallback rungs); {} keeps the registry's auto selection."""
    return {"prefill_attn": attn, "decode_attn": attn} if attn else {}


def _child_jax():
    """A rung child's jax, with the compile cache placed (utils/jax_cache:
    where ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed directory in
    the checkout) so sibling rungs share compiled programs."""
    import jax

    from deepspeedsyclsupport_tpu.utils.jax_cache import place_compile_cache

    place_compile_cache()
    return jax


# ======================================================================
# rung: probe
# ======================================================================
def run_probe():
    jax = _child_jax()
    dev = jax.devices()[0]
    _emit({"metric": "probe", "value": len(jax.devices()), "unit": "devices",
           "vs_baseline": 1.0, "detail": {"platform": dev.platform}})


# ======================================================================
# rung: kernels (compiled Pallas vs jnp oracle — TPU only)
# ======================================================================
def _rel_err(got, want):
    import numpy as np

    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(g - w)) / (np.max(np.abs(w)) + 1e-9))


def _bench_chain(fn_one, x0, extra_args, iters):
    """Per-iteration device time of ``fn_one(x, *extra) -> x'`` measured as
    ``iters`` data-dependent applications inside ONE jitted fori_loop — a
    single dispatch, so per-call dispatch latency (enough to swamp a sub-ms
    kernel) cancels out. The chained
    data dependency defeats CSE/DCE. The one-dispatch floor is measured
    separately and subtracted."""
    import jax
    from jax import lax

    def chained(x, extra):
        return lax.fori_loop(0, iters, lambda i, xx: fn_one(xx, *extra), x)

    def best_of(f, n=3):
        jax.block_until_ready(f(x0, extra_args))    # compile/warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x0, extra_args))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    total = best_of(jax.jit(chained))
    # dispatch floor: same structure, 1 iteration
    floor = best_of(jax.jit(lambda x, extra: fn_one(x, *extra)))
    if total <= floor or iters < 2:
        # dispatch jitter swamped the kernel — the difference of two noisy
        # samples is meaningless; report the per-dispatch bound honestly
        # instead of clamping to an absurd number
        return floor, "dispatch_bound"
    return (total - floor) / (iters - 1), "chained"


def _dense_attn_ref(q, k, v, causal=True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(d)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        m = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)


def _make_atoms(lens, bq, block_size, h, kvh, d, key, dtype):
    """Synthetic ragged prefill batch: one atom per bq-row chunk of each
    sequence, disjoint block tables, full-prefill positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bps = max(-(-ln // block_size) for ln in lens)
    pos0, qlen, atom_tbl = [], [], []
    next_blk = 0
    for ln in lens:
        nb = -(-ln // block_size)
        row = list(range(next_blk, next_blk + nb)) + [0] * (bps - nb)
        next_blk += nb
        for a0 in range(0, ln, bq):
            pos0.append(a0)
            qlen.append(min(bq, ln - a0))
            atom_tbl.append(row)
    slots = next_blk * block_size
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (len(pos0), bq, h, d), dtype)
    k = jax.random.normal(ks[1], (slots, kvh, d), dtype)
    v = jax.random.normal(ks[2], (slots, kvh, d), dtype)
    return (q, k, v, jnp.asarray(np.asarray(atom_tbl, np.int32)),
            jnp.asarray(pos0, dtype=jnp.int32),
            jnp.asarray(qlen, dtype=jnp.int32))


def run_kernels_micro():
    """<60s compiled-kernel evidence: ONE Pallas kernel (flash fwd), f32
    parity at small shape + bf16 throughput at production shape. Runs FIRST
    on TPU so even a cut run banks a compiled-kernel line
    (VERDICT r3 #1: three rounds with zero real-TPU evidence)."""
    jax = _child_jax()
    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.ops import flash_attention as fa

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit("kernels_micro requires TPU")
    peak = PEAKS[platform]
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()

    ks = jax.random.split(key, 3)
    q32 = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    got = jax.jit(lambda *a: fa.flash_attention(*a, causal=True))(
        q32, q32, q32)
    want = jax.jit(_dense_attn_ref)(q32, q32, q32)
    err = _rel_err(got, want)

    b, s, h, d = 4, 2048, 16, 128
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
    dt, how = _bench_chain(
        lambda x, k, v: fa.flash_attention(x, k, v, causal=True),
        q, (k, v), 10)
    tflops = 4 * b * h * s * s * d * 0.5 / dt / 1e12
    _emit({"metric": "kernel_micro_flash_fwd", "value": round(tflops, 2),
           "unit": "TFLOP/s",
           "vs_baseline": round(tflops * 1e12 / peak / REFERENCE_MFU, 4),
           "detail": {"platform": platform, "shape": [b, s, h, d],
                      "dtype": "bfloat16", "parity_max_rel_err": err,
                      "parity_ok": err < 5e-2, "timing": how,
                      "wall_s": round(time.perf_counter() - t0, 1),
                      "baseline": "fraction of chip peak vs reference "
                                  "54% MFU"}})


def run_kernels():
    jax = _child_jax()
    import functools

    import jax.numpy as jnp
    import numpy as np

    from deepspeedsyclsupport_tpu.ops import flash_attention as fa
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit("kernels rung requires TPU (interpret mode is not kernel "
                 "evidence)")
    peak, bw = PEAKS[platform], HBM_GBPS[platform]
    key = jax.random.PRNGKey(0)

    # -------- flash attention: parity (f32, with grads) ------------------
    ks = jax.random.split(key, 4)
    b, s, h, d = 2, 512, 4, 64
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    got = jax.jit(lambda *a: fa.flash_attention(*a, causal=True))(q, k, v)
    want = jax.jit(_dense_attn_ref)(q, k, v)
    fwd_err = _rel_err(got, want)

    def loss(f):
        return lambda q, k, v: (f(q, k, v) * v).astype(jnp.float32).sum()

    g_got = jax.jit(jax.grad(loss(
        lambda *a: fa.flash_attention(*a, causal=True)), (0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(loss(_dense_attn_ref), (0, 1, 2)))(q, k, v)
    bwd_err = max(_rel_err(a_, b_) for a_, b_ in zip(g_got, g_want))

    # -------- flash attention: throughput (bf16) -------------------------
    b, s, h, d = 4, 2048, 16, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
    dt, how = _bench_chain(
        lambda x, k, v: fa.flash_attention(x, k, v, causal=True),
        q, (k, v), 20)
    flops_fwd = 4 * b * h * s * s * d * 0.5  # 2 matmuls, causal half
    tflops = flops_fwd / dt / 1e12
    _emit({"metric": "kernel_flash_fwd", "value": round(tflops, 2),
           "unit": "TFLOP/s",
           "vs_baseline": round(tflops * 1e12 / peak / REFERENCE_MFU, 4),
           "detail": {"platform": platform, "shape": [b, s, h, d],
                      "dtype": "bfloat16", "parity_max_rel_err": fwd_err,
                      "parity_ok": fwd_err < 5e-2, "timing": how,
                      "baseline": "fraction of chip peak vs reference 54% MFU"}})

    bwd_one = jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
        .astype(jnp.float32).sum(), (0, 1, 2))

    def bwd_step(x, k, v):
        # fold dk/dv into the carry with an epsilon term so the dk/dv
        # pallas_call stays LIVE (chaining dq alone lets XLA dead-code the
        # second backward kernel and inflates the reported TFLOP/s)
        dq, dk, dv = bwd_one(x, k, v)
        eps = (dk.astype(jnp.float32).sum()
               + dv.astype(jnp.float32).sum()) * jnp.float32(1e-30)
        return (dq.astype(jnp.float32) + eps).astype(x.dtype)

    dt, how = _bench_chain(bwd_step, q, (k, v), 10)
    flops_fb = flops_fwd * 3.5  # grad call = fwd (2 matmuls) + bwd (5)
    tflops = flops_fb / dt / 1e12
    _emit({"metric": "kernel_flash_bwd", "value": round(tflops, 2),
           "unit": "TFLOP/s",
           "vs_baseline": round(tflops * 1e12 / peak / REFERENCE_MFU, 4),
           "detail": {"platform": platform, "shape": [b, s, h, d],
                      "dtype": "bfloat16", "parity_max_rel_err": bwd_err,
                      "parity_ok": bwd_err < 5e-2, "timing": how,
                      "baseline": "fraction of chip peak vs reference 54% MFU"}})

    # -------- ragged paged prefill: parity (f32, GQA) --------------------
    at = _make_atoms([96, 64, 33], 32, 16, 4, 2, 32, jax.random.PRNGKey(1),
                     jnp.float32)
    kern = functools.partial(pa.ragged_prefill_attention_pallas,
                             block_size=16)
    got = jax.jit(kern)(*at)
    want = jax.jit(functools.partial(pa.ragged_prefill_attention_reference,
                                     block_size=16))(*at)
    valid = np.asarray(jnp.arange(32)[None, :] < at[5][:, None])
    pre_err = _rel_err(np.asarray(got)[valid], np.asarray(want)[valid])

    # -------- ragged paged prefill: throughput (bf16) --------------------
    lens = [2048, 1536, 1024, 1024, 512, 512, 256, 256]
    at = _make_atoms(lens, 128, 64, 16, 16, 128, jax.random.PRNGKey(2),
                     jnp.bfloat16)
    pre_one = functools.partial(pa.ragged_prefill_attention_pallas,
                                block_size=64)
    dt, how = _bench_chain(lambda x, *rest: pre_one(x, *rest).astype(x.dtype),
                           at[0], tuple(at[1:]), 10)
    flops = sum(2 * 16 * 128 * ln * ln for ln in lens)  # causal half of 4
    tflops = flops / dt / 1e12
    _emit({"metric": "kernel_ragged_prefill", "value": round(tflops, 2),
           "unit": "TFLOP/s",
           "vs_baseline": round(tflops * 1e12 / peak / REFERENCE_MFU, 4),
           "detail": {"platform": platform, "seq_lens": lens,
                      "dtype": "bfloat16", "parity_max_rel_err": pre_err,
                      "parity_ok": pre_err < 5e-2, "timing": how,
                      "baseline": "fraction of chip peak vs reference 54% MFU"}})

    # -------- paged decode: parity (f32) then bandwidth (bf16) -----------
    def decode_setup(slots, bps, block, h, kvh, d, dtype, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        nb = slots * bps
        q = jax.random.normal(ks[0], (slots, h, d), dtype)
        kc = jax.random.normal(ks[1], (nb * block, kvh, d), dtype)
        vc = jax.random.normal(ks[2], (nb * block, kvh, d), dtype)
        tables = jnp.arange(nb, dtype=jnp.int32).reshape(slots, bps)
        lens_ = jnp.full((slots,), bps * block, jnp.int32)
        return q, kc, vc, tables, lens_

    args = decode_setup(4, 3, 16, 4, 2, 32, jnp.float32, 3)
    got = jax.jit(functools.partial(pa.paged_decode_attention_pallas,
                                    block_size=16))(*args)
    want = jax.jit(functools.partial(pa.paged_decode_attention_reference,
                                     block_size=16))(*args)
    dec_err = _rel_err(got, want)

    slots, bps, block, h, d = 64, 16, 64, 16, 128
    args = decode_setup(slots, bps, block, h, h, d, jnp.bfloat16, 4)
    dec_one = functools.partial(pa.paged_decode_attention_pallas,
                                block_size=block)
    dt, how = _bench_chain(lambda x, *rest: dec_one(x, *rest).astype(x.dtype),
                           args[0], tuple(args[1:]), 20)
    bytes_moved = slots * bps * block * h * d * 2 * 2  # K+V, bf16
    gbps = bytes_moved / dt / 1e9
    _emit({"metric": "kernel_paged_decode", "value": round(gbps, 1),
           "unit": "GB/s",
           "vs_baseline": round(gbps / bw, 4),
           "detail": {"platform": platform,
                      "slots": slots, "context": bps * block,
                      "dtype": "bfloat16", "parity_max_rel_err": dec_err,
                      "parity_ok": dec_err < 5e-2, "timing": how,
                      "baseline": "fraction of HBM peak bandwidth "
                                  "(decode attention is BW-bound)"}})


# ======================================================================
# rung: train (MFU ladder)
# ======================================================================
def model_flops_per_token(cfg):
    """6·N_active for the matmuls + attention quadratic term."""
    n_active = cfg.param_count()
    if cfg.num_experts > 0:
        dense_mlp = 3 * cfg.hidden_size * cfg.intermediate_size * cfg.num_layers
        n_active -= dense_mlp * (cfg.num_experts - cfg.num_experts_per_tok)
    attn = 12 * cfg.num_layers * cfg.hidden_size  # ≈ per token at seq S: *S below
    return 6 * n_active, attn


def _measure(name, seq, micro_bs, steps, remat, platform,
             attn_impl="auto", topo_axes=None):
    """One bench rung: build → warmup/compile → timed steps → metrics dict.
    Raises on OOM/compile failure; the caller's ladder steps down.

    Every rung now runs under telemetry with ``telemetry.mfu`` on: the
    warmup's third step is the captured clean-step window (outside the
    timed loop, so the one deliberately-synced step never pollutes
    tokens/s) and ``detail.mfu`` carries the full step-time attribution
    ledger — achieved MFU, the peak→roofline→measured waterfall and the
    per-region bound-by verdicts (docs/observability.md "MFU ledger")."""
    import shutil
    import tempfile

    # scratch telemetry/trace dir for this rung only: the ledger dict is
    # extracted before return, so the artifacts never outlive the attempt
    # (the OOM ladder retries would otherwise pile dirs up in /tmp)
    tdir = tempfile.mkdtemp(prefix="dstpu_bench_mfu_")
    try:
        return _measure_impl(name, seq, micro_bs, steps, remat, platform,
                             attn_impl, topo_axes, tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _measure_impl(name, seq, micro_bs, steps, remat, platform, attn_impl,
                  topo_axes, tdir):
    import jax
    import numpy as np

    import deepspeedsyclsupport_tpu as ds
    from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    cfg = get_config(name, remat=remat, max_seq_len=seq,
                     attn_impl=attn_impl)
    reset_world_topology()
    topo = ds.build_topology(**(topo_axes or {"dp": 1}))
    model = build_model(cfg)
    config = {
        "train_batch_size": micro_bs,
        "train_micro_batch_size_per_gpu": micro_bs,
        "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        # the ROADMAP MFU levers, explicit in the ENGINE config (not just
        # the model flag): remat via the activation_checkpointing section;
        # buffer donation is the fused train path's default and is VERIFIED
        # below by the analysis donation audit — a missed donation is a
        # silent HBM doubling that shrinks the ladder's feasible rungs
        "activation_checkpointing": {"enabled": remat},
        "steps_per_print": 10_000,
        "telemetry": {"enabled": True,
                      "output_dir": tdir,
                      "heartbeat": {"enabled": False},
                      "mfu": {"enabled": True, "step": 3}},
    }
    engine, _, _, _ = ds.initialize(model=model, config=config, topology=topo)
    batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(0),
                                             (micro_bs, seq), 0,
                                             cfg.vocab_size)}
    # 3 warmup steps: compile (1), warm (2), MFU window capture (3 — the
    # one synced step, deliberately before the timed loop). If step 3
    # recompiled, the engine re-arms the capture — DRAIN it here (bounded)
    # so the synced window never lands inside the timed loop below.
    for _ in range(3):
        m = engine.train_batch(batch)
    for _ in range(4):
        if not getattr(engine, "_mfu_pending", False):
            break
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0

    tokens = steps * micro_bs * seq
    tok_per_sec = tokens / dt
    f_matmul, f_attn = model_flops_per_token(cfg)
    flops_per_token = f_matmul + f_attn * seq
    achieved = tok_per_sec * flops_per_token
    # a platform without a published peak (the CPU sim) has no MFU
    mfu = achieved / PEAKS[platform] if platform in PEAKS else None
    # donation audit (analysis/donation.py) on the exact compiled step we
    # just timed — re-lowering is a compile-cache hit. Outside the timed
    # window; best-effort (the bench contract: never die on telemetry).
    try:
        rep = engine.graph_report(analyzers=("donation",))["donation"]
        donation = {"ok": rep.ok, "donated": len(rep.donated),
                    "missed": len(rep.not_donated),
                    "wasted_bytes": rep.wasted_bytes}
    except Exception as e:
        donation = {"ok": None, "error": str(e)[:200]}
    # the MFU ledger from the captured window (same never-die contract)
    try:
        ledger = engine.mfu_ledger()
        ledger.pop("window", None)
    except Exception as e:
        ledger = {"error": str(e)[:200]}
    try:
        engine.telemetry.close("bench")
    except Exception:
        pass
    return {
        "metric": f"train_tokens_per_sec_per_chip_{name}_seq{seq}",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": None if mfu is None else round(mfu / REFERENCE_MFU, 4),
        "detail": {"platform": platform,
                   # detail.mfu is the LEDGER (dict) from this round on;
                   # the headline scalar (tok/s-derived fraction of chip
                   # peak, the pre-ledger detail.mfu) moves to mfu_headline
                   "mfu": ledger,
                   "mfu_headline": None if mfu is None else round(mfu, 4),
                   "tflops": round(achieved / 1e12, 2),
                   "micro_bs": micro_bs, "remat": remat,
                   "donation": donation,
                   "attn_impl": attn_impl,
                   "baseline": "achieved MFU vs reference 54% (Ulysses "
                               "175/312 TFLOPs on A100)",
                   "loss": round(float(np.asarray(m["loss"])), 4)},
    }


def run_train():
    jax = _child_jax()

    platform = jax.devices()[0].platform
    if platform == "tpu":
        # memory ladder for one 16GB v5e chip: fp32 master + Adam moments +
        # fp32 grads peak at 16 bytes/param, so llama2-1b (~0.94B) is right
        # at the edge — try it, then step down to the 650M config that fits
        # with headroom (bigger micro-batch, and a no-remat rung that trades
        # the recompute pass for activation memory)
        ladder = [
            ("llama2-1b", 1024, 4, 8, True),
            ("llama2-1b", 1024, 2, 8, True),
            ("llama-650m", 1024, 8, 8, False),
            ("llama-650m", 1024, 8, 8, True),
            ("llama-650m", 1024, 4, 8, True),
        ]
    else:
        ladder = [("tiny", 256, 8, 4, False)]

    import gc

    t_start = time.monotonic()
    # variants must START early enough to FINISH inside the parent's
    # _spawn timeout (1200 s): a variant is a fresh compile (~2 min) +
    # timed steps, so leave ~half the window as headroom — an optional
    # A-B overrunning the child would cost the rung its whole result
    budget = float(os.environ.get("DSTPU_TRAIN_BUDGET", 600))
    last_err = None
    base = None
    for name, seq, micro, steps, remat in ladder:
        try:
            r = _measure(name, seq, micro, steps, remat, platform)
            _emit(r)
            base = (name, seq, micro, steps, remat, r)
            break
        except Exception as e:  # OOM / compile failure → next rung
            last_err = f"{name} micro={micro} remat={remat}: {str(e)[:300]}"
            print(f"bench rung failed: {last_err}", file=sys.stderr)
        # drop the failed rung's buffers before the next attempt (the
        # exception traceback pins the engine's frames until cleared)
        gc.collect()
        jax.clear_caches()
    if base is None:
        raise RuntimeError(f"all train rungs failed; last: {last_err}")
    # A-B the big perf levers inside the remaining budget: attention impl
    # (flash Pallas vs XLA's fused attention at this seq) and remat off
    # (recompute pass vs activation memory). The parent headlines the BEST
    # train line, so a faster variant directly moves the round's number.
    if platform == "tpu":
        name, seq, micro, steps, remat, _ = base
        # long context: the reference's 54% MFU bar is a LONG-SEQUENCE
        # (Ulysses) number, and both flash and MFU improve with seq — the
        # seq-4k rung is the apples-to-apples comparison. It runs FIRST
        # only when tokens/step stay equal (micro/4 >= 1); on a memory-edge
        # base (micro < 4) 4096 tokens/step would exceed the base and a
        # likely OOM's wasted compile would eat the other variants' budget
        variants = [("xla_attn", dict(attn_impl="xla"))]
        if micro >= 4:
            variants.insert(0, ("seq4k", dict(seq=4096, micro=micro // 4)))
        else:
            variants.append(("seq4k", dict(seq=4096, micro=1)))
        if remat:
            variants.append(("noremat", dict(remat=False)))
        for tag, kw in variants:
            if time.monotonic() - t_start > budget:
                print("train variant skipped (budget)", file=sys.stderr)
                break
            # free the previous engine's executables/caches BEFORE the
            # next full compile — llama2-1b sits at the edge of the chip
            gc.collect()
            jax.clear_caches()
            try:
                r = _measure(name, kw.get("seq", seq),
                             kw.get("micro", micro), steps,
                             kw.get("remat", remat), platform,
                             attn_impl=kw.get("attn_impl", "auto"))
                r["metric"] += f"_{tag}"  # unique metric per variant
                _emit(r)
            except Exception as e:
                print(f"train variant {tag} failed: {str(e)[:200]}",
                      file=sys.stderr)


# ======================================================================
# rung: train_ring (ring-attention attn_impl A/B under the MFU ledger)
# ======================================================================
def run_train_ring():
    """Ring-attention ``attn_impl`` A/B on a seq-sharded 2-device mesh
    (CPU sim): the inline online-softmax ring (``ring:xla``) vs the
    Pallas-flash per-block path (``ring:flash`` — interpret mode off-TPU,
    so CPU prices dispatch structure, not kernel speed). Both arms run
    under the MFU ledger, so each line's ``detail.mfu`` carries the
    per-region attention time — the A/B the ROADMAP's long-sequence item
    needs before the real-TPU run."""
    jax = _child_jax()

    platform = jax.devices()[0].platform
    if len(jax.devices()) < 2:
        _emit({"metric": "train_ring_skipped", "value": 0.0, "unit": "arms",
               "vs_baseline": 0.0,
               "detail": {"platform": platform,
                          "reason": "needs >= 2 devices for the seq mesh"}})
        return
    for tag, impl, seq, micro, steps in (
            ("xla", "ring:xla", 256, 4, 2),
            ("flash", "ring:flash", 256, 4, 2)):
        try:
            r = _measure("tiny", seq, micro, steps, False, platform,
                         attn_impl=impl, topo_axes={"dp": 1, "sp": 2})
            r["metric"] = f"train_ring_{tag}_tokens_per_sec_per_chip"
            _emit(r)
        except Exception as e:
            print(f"train_ring arm {tag} failed: {str(e)[:300]}",
                  file=sys.stderr)


# ======================================================================
# rung: multichip (pod-scope comm/compute decomposition on the CPU sim)
# ======================================================================
def run_multichip():
    """8-virtual-device ZeRO-3 training with per-rank flight recorders and
    the static collective census, fused by ``monitor/pod.py``, A-B'd
    full-precision vs quantized collectives (ZeRO++ qwZ int8 weight
    all-gather + qgZ int8 grad all-to-all-reduce, ``comm/quantized.py``
    via ``runtime/zeropp.py``): the per-traffic-class
    ``class_bytes_per_step`` ratios and the ``comm_bound_frac`` delta ARE
    the wire-byte proof the ROADMAP's quantized-collectives item asks for
    — byte totals in each arm's table match its static census, so the
    quantized arm shows up as a bytes (and bandwidth-demand) drop at
    equal step semantics."""
    import importlib.util
    import tempfile

    n = int(os.environ.get("DSTPU_MULTICHIP_DEVICES", "8"))
    # no XLA_FLAGS juggling here: pod_leg's _force_cpu_if_needed sets the
    # virtual device count before this child's first jax import
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "__graft_entry__.py"))
    graft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(graft)
    t0 = time.perf_counter()

    def arm(tag, td, zero_config):
        from deepspeedsyclsupport_tpu.comm.topology import (
            reset_world_topology)

        reset_world_topology()
        report = graft.pod_leg(n, os.path.join(td, f"telemetry_{tag}"),
                               steps=6, emit_metrics_line=False,
                               zero_config=zero_config)
        dec = report["decomposition"]
        return {
            "n_steps": report["n_steps"],
            "ranks": len(report["ranks"]),
            "comm_bound_frac": round(dec["comm_bound_frac"] or 0.0, 4),
            "per_class_bandwidth_gbps": {
                cls: row["effective_gbps"]
                for cls, row in dec["classes"].items()},
            "class_bytes_per_step": {
                cls: row["bytes_per_step"]
                for cls, row in dec["classes"].items()},
            "exposed_comm_s": dec["exposed_comm_s"],
            "compute_floor_s": dec["compute_floor_s"],
            "census_bytes_match": report["census"]["bytes_match"],
            "skew_p95_s": report["skew"]["p95"],
        }

    def dense_arm(tag, td, zero_config):
        """One quantized-A/B arm on a DENSE model (no internal sharding
        constraints): the ZeRO++ shard_map step rejects the transformer's
        in-graph constraints on this jax version (pre-existing zeropp
        limitation — its test suite runs dense models for the same
        reason), and the wire-byte proof is about the collectives, not
        the model. Both arms run THIS model, so the ratio is apples to
        apples."""
        import jax
        import numpy as np

        import deepspeedsyclsupport_tpu as ds
        from deepspeedsyclsupport_tpu.comm.topology import (
            reset_world_topology)
        from deepspeedsyclsupport_tpu.monitor import pod as pod_lib

        reset_world_topology()
        devs = jax.devices()[:n]
        fsdp = 2 if n % 2 == 0 else 1
        topo = ds.build_topology(devices=devs, dp=n // fsdp, fsdp=fsdp)

        class RectModel:
            def init_params(self):
                rng = np.random.default_rng(0)
                return {"w": rng.normal(0, 0.1, (256, 2048))
                        .astype(np.float32),
                        "b": np.zeros((2048,), np.float32)}

            def loss(self, params, batch, rng):
                import jax.numpy as jnp

                y = jnp.tanh(batch["x"] @ params["w"] + params["b"])
                return jnp.mean((y - batch["y"]) ** 2)

        tdir = os.path.join(td, f"telemetry_{tag}")
        dp_ws = max(topo.get_data_parallel_world_size(), 1)
        config = {
            "train_batch_size": 2 * dp_ws,
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": dict(zero_config),
            "steps_per_print": 10_000,
            "comms_logger": {"enabled": True},
            "telemetry": {"enabled": True, "output_dir": tdir,
                          "heartbeat": {"enabled": False},
                          "memory_interval_steps": 0},
        }
        engine, _, _, _ = ds.initialize(model=RectModel(), config=config,
                                        topology=topo)
        rng = np.random.default_rng(1)
        bs = engine.train_batch_size()
        batch = {k: jax.device_put(v, engine.topology.data_sharding(v.ndim))
                 for k, v in
                 {"x": rng.normal(0, 1, (bs, 256)).astype(np.float32),
                  "y": rng.normal(0, 1, (bs, 2048)).astype(np.float32)
                  }.items()}
        for _ in range(6):
            engine.train_batch(batch)
        engine.emit_comm_census()
        engine.telemetry.close(f"multichip_{tag}")
        report = pod_lib.pod_report_from_paths([tdir])
        d = report.to_dict()
        dec = d["decomposition"]
        return {
            "comm_bound_frac": round(dec["comm_bound_frac"] or 0.0, 4),
            "class_bytes_per_step": {
                cls: row["bytes_per_step"]
                for cls, row in dec["classes"].items()},
            "per_class_bandwidth_gbps": {
                cls: row["effective_gbps"]
                for cls, row in dec["classes"].items()},
            "census_bytes_match": d["census"]["bytes_match"],
        }

    import jax

    with tempfile.TemporaryDirectory(prefix="dstpu_bench_pod_") as td:
        fp = arm("fp", td, {"stage": 3})
        _emit({"metric": "multichip_comm_bound_frac_fp", "value":
               fp["comm_bound_frac"], "unit": "frac", "vs_baseline": None,
               "detail": {"platform": jax.devices()[0].platform,
                          "partial": True, **fp}})
        # quantized A/B: identical dense model/batch/steps per arm, so any
        # bytes delta is the TRANSPORT (qwZ int8 weight all-gather + qgZ
        # int8 grad all-to-all quant-reduce), not the workload
        try:
            ab = {"fp": dense_arm("dense_fp", td, {"stage": 3}),
                  "quantized": dense_arm(
                      "dense_q", td,
                      {"stage": 3, "zero_quantized_weights": True,
                       "zero_quantized_gradients": True})}
        except Exception as e:  # the A/B detail must never eat the rung
            ab = {"error": str(e)[:300]}
    detail = {
        "platform": jax.devices()[0].platform,
        "n_devices": n,
        **fp,
        "quantized_ab": ab,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if "error" not in ab:
        # the wire-byte proof: per-class quantized/full-precision byte
        # ratio (int8 payload + block scales vs fp32 on these arms) and
        # the comm-boundedness delta at equal step semantics
        ratios = {}
        for cls, fp_bytes in ab["fp"]["class_bytes_per_step"].items():
            q_bytes = ab["quantized"]["class_bytes_per_step"].get(cls)
            if q_bytes is not None and fp_bytes:
                ratios[cls] = round(q_bytes / fp_bytes, 4)
        detail["quantized_bytes_ratio_by_class"] = ratios
        detail["comm_bound_frac_delta"] = round(
            ab["quantized"]["comm_bound_frac"]
            - ab["fp"]["comm_bound_frac"], 4)
        detail["total_bytes_ratio"] = round(
            sum(ab["quantized"]["class_bytes_per_step"].values())
            / max(sum(ab["fp"]["class_bytes_per_step"].values()), 1e-9), 4)
    _emit({
        "metric": "multichip_comm_bound_frac",
        "value": fp["comm_bound_frac"],
        "unit": "frac", "vs_baseline": None,
        "detail": detail})


# ======================================================================
# rung: offload (beyond-HBM: bucketed D2H / host-Adam / H2D pipeline)
# ======================================================================
def run_offload():
    """In-HBM vs cpu vs nvme offload arms at a model whose fp32 training
    state (master + moments + grads, 16 B/param) exceeds a notional HBM
    budget — the ZeRO-Infinity story on the CPU sim. Headlines the
    step-time overhead ratio of offloading and the pipeline's overlap
    efficiency (1 − exposed/total transfer time,
    ``runtime/offload_pipeline.py``); the nvme arm additionally proves the
    bounded moment window (host-RAM high-water ≤ the configured bound)."""
    jax = _child_jax()
    import gc
    import tempfile

    import numpy as np

    import deepspeedsyclsupport_tpu as ds
    from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    platform = jax.devices()[0].platform
    budget_mb = float(os.environ.get("DSTPU_OFFLOAD_HBM_BUDGET_MB", "48"))
    hidden = int(os.environ.get("DSTPU_OFFLOAD_HIDDEN", "288"))
    layers = int(os.environ.get("DSTPU_OFFLOAD_LAYERS", "3"))
    seq, micro_bs, steps, warm = 256, 4, 4, 1
    mcfg = get_config("tiny", hidden_size=hidden,
                      intermediate_size=3 * hidden, num_layers=layers,
                      num_heads=4, num_kv_heads=4, vocab_size=4096,
                      max_seq_len=seq)
    n_params = mcfg.param_count()
    state_bytes = 16 * n_params  # fp32 master + m + v + grads
    bucket = int(os.environ.get("DSTPU_OFFLOAD_BUCKET", 2 * 2 ** 20))

    def arm(tag, zero_cfg, telemetry_dir=None):
        reset_world_topology()
        topo = ds.build_topology(dp=1)
        model = build_model(mcfg)
        config = {
            "train_batch_size": micro_bs,
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": zero_cfg,
            "steps_per_print": 10_000,
        }
        if telemetry_dir is not None:
            # goodput evidence for the offload_stall bucket (accounting
            # must stay >= 99% with the new category in play)
            config["telemetry"] = {"enabled": True,
                                   "output_dir": telemetry_dir,
                                   "heartbeat": {"enabled": False}}
        engine, _, _, _ = ds.initialize(model=model, config=config,
                                        topology=topo)
        batch = {"input_ids": jax.random.randint(
            jax.random.PRNGKey(0), (micro_bs, seq), 0, mcfg.vocab_size)}
        for _ in range(warm):
            m = engine.train_batch(batch)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            m = engine.train_batch(batch)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / steps
        out = {"step_s": round(dt, 4),
               "loss": round(float(np.asarray(m["loss"])), 4)}
        mh = engine._mh_offload
        if mh is not None:
            s = mh.offload_summary()
            out["offload"] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in s.items()}
            out["overlap_efficiency"] = round(s["overlap_efficiency"], 4)
            if "window_bound_bytes" in s:  # nvme arm only
                out["window_bounded"] = bool(
                    s["window_hwm_bytes"] <= s["window_bound_bytes"])
        if engine.telemetry is not None and engine.telemetry.goodput:
            g = engine.telemetry.goodput.summary()
            known = sum(g.get(c, 0.0)
                        for c in engine.telemetry.goodput.CATEGORIES)
            out["goodput"] = {
                "accounted": round(known / g["total"], 4),
                "offload_stall_s": round(g.get("offload_stall", 0.0), 4)}
            engine.telemetry.close()
        del engine
        gc.collect()
        jax.clear_caches()
        return out

    with tempfile.TemporaryDirectory(prefix="dstpu_bench_offload_") as td:
        arms = {"hbm": arm("hbm", {"stage": 0})}
        arms["cpu"] = arm("cpu", {
            "stage": 2,
            "offload_optimizer": {"device": "cpu", "bucket_size": bucket}},
            telemetry_dir=os.path.join(td, "telemetry"))
        arms["nvme"] = arm("nvme", {
            "stage": 2,
            "offload_optimizer": {"device": "nvme", "bucket_size": bucket,
                                  "buffer_count": 2,
                                  "nvme_path": os.path.join(td, "swap")}})
    for tag, a in arms.items():
        _emit({"metric": f"offload_step_s_{tag}", "value": a["step_s"],
               "unit": "s", "vs_baseline": None,
               "detail": {"platform": platform, "partial": True, **a}})
    ratio = round(arms["cpu"]["step_s"] / max(arms["hbm"]["step_s"], 1e-9), 3)
    _emit({
        "metric": "offload_overhead_ratio",
        "value": ratio,
        "unit": "x", "vs_baseline": None,
        "detail": {
            "platform": platform,
            "baseline": "offloaded (cpu arm) vs in-HBM step time; "
                        "ZeRO-Infinity's bar is overhead hidden behind "
                        "overlap, bounded-memory tiers",
            "n_params": n_params,
            "state_mb": round(state_bytes / 2**20, 1),
            "hbm_budget_mb": budget_mb,
            "exceeds_budget": bool(state_bytes > budget_mb * 2**20),
            "bucket_bytes": bucket,
            "nvme_overhead_ratio": round(
                arms["nvme"]["step_s"] / max(arms["hbm"]["step_s"], 1e-9),
                3),
            "overlap_efficiency_cpu": arms["cpu"].get("overlap_efficiency"),
            "overlap_efficiency_nvme": arms["nvme"].get(
                "overlap_efficiency"),
            "meets_overlap_floor": bool(
                (arms["cpu"].get("overlap_efficiency") or 0.0) >= 0.5),
            "window_bounded": arms["nvme"].get("window_bounded"),
            "goodput": arms["cpu"].get("goodput"),
            "arms": arms,
        }})


# ======================================================================
# rung: serve (FastGen-style TTFT / throughput, SplitFuse A-B)
# ======================================================================
def _request_waterfall(session_traces, router_records=()):
    """Per-load-point request-time attribution (``detail.request_waterfall``):
    join the in-memory trace rings drained from the point's sessions (plus
    the router's, in the fleet rung) through ``monitor.reqtrace`` and
    compact the payload for a bench line — ``bench_diff`` gates the
    per-stage TTFT p95s in it."""
    from deepspeedsyclsupport_tpu.monitor import reqtrace

    try:
        att = reqtrace.waterfall(
            [(rid, "", list(recs)) for rid, recs in session_traces],
            router_records=list(router_records))
    except Exception as e:  # attribution is a detail, never the rung
        return {"error": str(e)[:200]}
    att["slo_burn"].pop("windows", None)  # per-window rows are report fuel
    att["worst"] = att["worst"][:3]
    return att


def _drive_serving(eng, prompts, n_clients, reqs_per_client, gen_len, mode,
                   uid_base, arrival_of=None, deadline=None):
    """Closed-loop clients over the v2 engine at single-forward granularity.

    mode="splitfuse": decode tokens and (chunked) prompt tokens fuse into
    the same forward — the SplitFuse schedule. mode="naive": a waiting
    prompt preempts decoding and prefills to completion first (the
    static-batching behavior the FastGen blog A-Bs against,
    ``blogs/deepspeed-fastgen/README.md:139``).

    ``arrival_of``: uid → seconds-after-start arrival offset. Staggered
    first arrivals create the steady-state mix the blog measures — prompts
    landing WHILE other clients decode (an all-at-t0 burst lets the naive
    arm batch every prefill upfront and never preempt a decode, which is
    not the scenario the SplitFuse claim is about). A request's TTFT clock
    starts at its arrival.

    ``deadline`` (``time.perf_counter()`` base): overrunning it raises
    :class:`_ScenarioTimeout` so the caller keeps earlier scenarios'
    results instead of losing the whole rung to one slow load point.
    """
    import jax.numpy as jnp

    arrival_of = arrival_of or {}

    ttfts, itls = [], []
    submitted, last_tok, gen_count = {}, {}, {}
    live, waiting = {}, []
    pending_tok = {}    # uid -> sampled decode token not yet admitted
    awaiting = set()    # uids with a forward in flight (fresh logits coming)
    ttft_done = set()
    ttft_of = {}        # uid -> measured TTFT (goodput-rung SLA input)
    next_req = [0] * n_clients
    finished = evicted = evicted_tokens = total_decoded = stall_guard = 0
    total = n_clients * reqs_per_client
    req_stats = []      # (submit_t, done_t, tokens, was_evicted) per request

    def submit(c, now):
        i = next_req[c]
        next_req[c] += 1
        uid = uid_base + c * 1000 + i
        waiting.append((uid, c))
        submitted[uid] = max(now, t0 + arrival_of.get(uid, 0.0))

    def arrived(uid, now):
        return submitted[uid] <= now

    def retire(uid, now, was_evicted=False):
        nonlocal finished
        c = live.pop(uid)
        eng.flush([uid])
        pending_tok.pop(uid, None)
        awaiting.discard(uid)
        finished += 1
        req_stats.append((submitted[uid], now, gen_count.get(uid, 0),
                          was_evicted, ttft_of.get(uid, 0.0)))
        if next_req[c] < reqs_per_client:
            submit(c, now)

    # pre-warm the device argmax/max executables OUTSIDE the timed window
    # (they are new eager dispatches per logits shape; their first-call
    # compile must not land in the naive arm's first TTFT/ITL samples)
    warm = eng.put([uid_base - 1], [[1, 2, 3]])[uid_base - 1]
    float(jnp.max(warm))
    int(jnp.argmax(warm))
    eng.flush([uid_base - 1])
    # snapshot AFTER the warmup so its dispatches stay out of the metrics
    dispatches0 = getattr(eng, "host_dispatches", 0)

    t0 = time.perf_counter()
    for c in range(n_clients):
        submit(c, t0)
    while finished < total:
        now = time.perf_counter()
        if deadline is not None and now > deadline:
            raise _ScenarioTimeout(
                f"{mode}: scenario deadline after {finished}/{total} "
                f"requests ({total_decoded} tokens)")
        # prompts first in naive mode: they preempt and fully prefill
        if mode == "naive" and waiting:
            admit_u, admit_t = [], []
            while waiting:
                uid, c = waiting[0]
                if not arrived(uid, now):
                    break
                res = eng.check_schedule(admit_u + [uid],
                                         [len(t) for t in admit_t]
                                         + [len(prompts[uid])])
                if uid in res.rejected:
                    break
                waiting.pop(0)
                admit_u.append(uid)
                admit_t.append(prompts[uid])
                live[uid] = c
            if admit_u:
                eng.put(admit_u, admit_t, drain=True)  # decode stalls
                # logits are device-resident and put() is async-dispatch:
                # force completion BEFORE stamping TTFT (scalar fetch — a
                # full-logits pull would add V*4B per seq of host
                # traffic to the timed path)
                for uid in admit_u:
                    lg = eng.query(uid)
                    if lg is not None:
                        float(jnp.max(lg))
                now = time.perf_counter()
                for uid in admit_u:
                    ttfts.append(now - submitted[uid])
                    ttft_of[uid] = now - submitted[uid]
                    ttft_done.add(uid)
                    last_tok[uid] = now
                    gen_count[uid] = 0
                    awaiting.add(uid)
                stall_guard = 0
                continue
        # consume fresh logits: sample one token per drained live sequence
        for uid in list(live):
            if uid not in awaiting:
                continue
            lg = eng.query(uid)
            if lg is None:
                continue
            awaiting.discard(uid)
            # device-side argmax: the sampled token (one scalar) is all
            # that crosses to the host — matching real serving, where the
            # sampler lives on device; the int() fetch is the barrier that
            # makes the timestamp honest
            tok = int(jnp.argmax(lg))
            now = time.perf_counter()
            if uid not in ttft_done:      # prompt just drained (splitfuse)
                ttfts.append(now - submitted[uid])
                ttft_of[uid] = now - submitted[uid]
                ttft_done.add(uid)
            else:
                itls.append(now - last_tok[uid])
            last_tok[uid] = now
            gen_count[uid] += 1
            total_decoded += 1
            if gen_count[uid] >= gen_len:
                retire(uid, now)
            else:
                pending_tok[uid] = tok
        put_uids = list(pending_tok)
        put_toks = [[pending_tok[u]] for u in put_uids]
        if mode == "splitfuse":
            while waiting:
                uid, c = waiting[0]
                if not arrived(uid, now):
                    break
                res = eng.check_schedule(put_uids + [uid],
                                         [len(t) for t in put_toks]
                                         + [len(prompts[uid])])
                if uid in res.rejected:
                    break
                waiting.pop(0)
                put_uids.append(uid)
                put_toks.append(prompts[uid])
                live[uid] = c
                gen_count[uid] = 0
        in_flight = any(d.pending for d in eng.seqs.values())
        if not put_uids and not in_flight:
            # quiet because the next request hasn't ARRIVED yet (staggered
            # load): idle-wait to its arrival — that is offered-load slack,
            # not a scheduler stall
            future = [submitted[u] for u, _ in waiting
                      if not arrived(u, now)]
            if future and not live:
                wake = min(future) if deadline is None \
                    else min(min(future), deadline)
                time.sleep(max(0.0, wake - time.perf_counter()))
                stall_guard = 0
                continue
            stall_guard += 1
            if stall_guard > 3:
                raise RuntimeError(
                    f"serving loop stalled: {len(waiting)} waiting, "
                    f"{len(live)} live, {finished}/{total} done")
            continue
        res = eng.put(put_uids, put_toks, drain=False)
        for uid in res.admission.admitted:
            if uid in pending_tok:
                del pending_tok[uid]
            awaiting.add(uid)
        # KV-pool pressure: a rejected decode token means its sequence can't
        # grow — evict the longest-context live sequence (truncation, like
        # generate()) so decode always progresses; tokens are only counted
        # when a forward actually ran for them
        if (pending_tok and not res.admission.admitted and not in_flight):
            victim = max(live, key=lambda u: eng.seqs[u].n_cached
                         if u in eng.seqs else -1)
            # an evicted request finished with < gen_len tokens: exclude its
            # tokens from the throughput numerator so the A-B arms compare
            # EQUAL work (finished requests x gen_len each) even if their
            # eviction rates differ
            evicted_tokens += gen_count.get(victim, 0)
            retire(victim, now, was_evicted=True)
            evicted += 1
        stall_guard = 0
    wall = time.perf_counter() - t0
    return _serving_result(wall, total, evicted, total_decoded,
                           evicted_tokens, ttfts, itls,
                           getattr(eng, "host_dispatches", 0) - dispatches0,
                           req_stats)


def _serving_result(wall, total, evicted, total_decoded, evicted_tokens,
                    ttfts, itls, dispatches, req_stats):
    """One result-dict schema for every serving arm — the A-B comparison
    depends on both arms computing percentiles/goodput identically."""
    ttfts = sorted(ttfts)
    itls = sorted(itls)

    def pct(xs, p):
        return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else 0.0

    counted = total_decoded - evicted_tokens
    itl_mean = sum(itls) / len(itls) if itls else 0.0
    itl_var = (sum((x - itl_mean) ** 2 for x in itls) / len(itls)
               if itls else 0.0)
    return {"wall_s": round(wall, 3),
            "requests": total,
            "evicted": evicted,
            "tokens_generated": counted,
            "tokens_evicted": evicted_tokens,
            "throughput_tok_s": round(counted / max(wall, 1e-9), 2),
            "ttft_p50_s": round(pct(ttfts, 0.50), 4),
            "ttft_p95_s": round(pct(ttfts, 0.95), 4),
            "itl_p50_s": round(pct(itls, 0.50), 4),
            "itl_p95_s": round(pct(itls, 0.95), 4),
            "itl_std_s": round(itl_var ** 0.5, 5),
            "host_dispatches": dispatches,
            "host_dispatches_per_token": round(dispatches / max(counted, 1),
                                               3),
            "req_stats": req_stats}


def _drive_serving_sla(eng, prompts, n_clients, reqs_per_client, gen_len,
                       uid_base, arrival_of=None, deadline=None,
                       ttft_sla=None, rate_sla=None, capacity=None,
                       journal_dir=None, crash_at_tokens=None):
    """Closed-loop clients over the SLA serving policy layer
    (``inference/v2/serving.ServingSession``) — the third arm next to
    ``_drive_serving``'s naive/splitfuse: admission control (queue/shed),
    slack-ordered batch composition, lowest-slack KV preemption, and fused
    K-step decode whenever every live stream is in steady state.

    ``journal_dir`` + ``crash_at_tokens`` turn the drive into the
    AVAILABILITY arm: requests are journaled, and once ``crash_at_tokens``
    total tokens have been emitted the serving replica "dies" mid-decode —
    KV state, descriptors and all session policy state are dropped; a
    replacement session on the warm engine replays the journal from each
    stream's emitted-token watermark and the drive continues. The wall
    clock keeps running through the failover, so goodput-with-recovery
    honestly includes the recovery gap. (A warm replacement isolates the
    REPLAY cost; the cold-start path — process death, restart, compile —
    is the supervisor e2e's job, ``tests/unit/test_serving_resilience``.)

    Returns the same result dict as ``_drive_serving`` plus a ``serve``
    sub-dict (admitted/queued/shed/evicted counters and ``shed_pct``). A
    shed request enters ``req_stats`` with zero tokens and the evicted flag
    — an SLA miss — so goodput compares EQUAL offered load across arms;
    graceful degradation shows up as shed_pct rising while goodput stays
    above zero, instead of every stream missing together (r05 at 10
    clients). Token timestamps come from the session's event stream; a
    fused burst of k tokens lands at one instant and contributes k ITL
    samples of delta/k (the amortized steady-state rate — per-token
    intervals inside one device dispatch are not observable by design)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeedsyclsupport_tpu.inference.sampling import SamplingParams
    from deepspeedsyclsupport_tpu.inference.v2 import (ServingPolicyConfig,
                                                       ServingSession)

    from deepspeedsyclsupport_tpu.inference.v2.supervisor import journal_path

    arrival_of = arrival_of or {}
    have_sla = ttft_sla is not None or bool(rate_sla)
    pol = ServingPolicyConfig(
        admission="sla" if have_sla else "none",
        ttft_sla_s=ttft_sla, token_rate_sla=rate_sla or 0.0,
        shed_policy="queue", preempt_policy="reject",
        max_queue_s=(4.0 * ttft_sla if ttft_sla else 60.0),
        journal_path=(journal_path(journal_dir, attempt=0)
                      if journal_dir else None))
    # `capacity` is SHARED across the sweep's arms: the solo calibration
    # run measures real prefill/decode rates into it, so the admission gate
    # at every load point projects from measurements, not priors
    sess = ServingSession(eng, pol, capacity=capacity)
    crashed = False
    recovery_summary = None
    trace_records = []

    ttfts, itls = [], []
    submitted, last_tok, gen_count, ttft_of = {}, {}, {}, {}
    client_of = {}
    next_req = [0] * n_clients
    finished = evicted = shed = evicted_tokens = total_decoded = 0
    stall_guard = 0
    total = n_clients * reqs_per_client
    req_stats = []
    due = []  # (when, uid, client) arrivals not yet submitted

    # pre-warm the sampler executable OUTSIDE the timed window (first-call
    # compile must not land in the first TTFT/ITL samples)
    eng.put([uid_base - 1], [[1, 2, 3]])
    eng.sample_drained([uid_base - 1], jax.random.PRNGKey(0),
                       SamplingParams())
    eng.flush([uid_base - 1])
    dispatches0 = getattr(eng, "host_dispatches", 0)

    t0 = time.perf_counter()

    def queue_next(c, when):
        i = next_req[c]
        next_req[c] += 1
        uid = uid_base + c * 1000 + i
        due.append((when, uid, c))
        client_of[uid] = c

    def record_done(uid, now, was_evicted):
        nonlocal finished
        finished += 1
        req_stats.append((submitted[uid], now, gen_count.get(uid, 0),
                          was_evicted, ttft_of.get(uid, 0.0)))
        c = client_of[uid]
        if next_req[c] < reqs_per_client:
            queue_next(c, now)  # closed loop: next request on completion

    for c in range(n_clients):
        queue_next(c, t0 + arrival_of.get(uid_base + c * 1000 + 0, 0.0))

    while finished < total:
        now = time.perf_counter()
        if deadline is not None and now > deadline:
            raise _ScenarioTimeout(
                f"sla: scenario deadline after {finished}/{total} requests "
                f"({total_decoded} tokens, {shed} shed)")
        for when, uid, c in [d for d in due if d[0] <= now]:
            due.remove((when, uid, c))
            submitted[uid] = max(now, when)
            gen_count[uid] = 0
            if sess.submit(uid, prompts[uid], gen_len, now=now) == "shed":
                shed += 1
                record_done(uid, now, was_evicted=True)
        events = sess.step()
        for ev in events:
            if ev.kind == "token":
                uid = ev.uid
                n = len(ev.tokens)
                if uid not in ttft_of:
                    ttft_of[uid] = ev.t - submitted[uid]
                    ttfts.append(ttft_of[uid])
                    # tokens after the first in the SAME burst ride the
                    # prefill drain: no ITL samples for them
                else:
                    itl = (ev.t - last_tok[uid]) / n
                    itls.extend([itl] * n)
                last_tok[uid] = ev.t
                gen_count[uid] += n
                total_decoded += n
            elif ev.kind == "finish":
                was_evicted = ev.reason == "evicted"
                if was_evicted:
                    evicted += 1
                    evicted_tokens += gen_count.get(ev.uid, 0)
                record_done(ev.uid, ev.t, was_evicted)
            elif ev.kind == "shed":
                shed += 1
                record_done(ev.uid, ev.t, was_evicted=True)
        if (crash_at_tokens is not None and not crashed
                and total_decoded >= crash_at_tokens):
            # ------- injected replica death + journal-replay failover
            import dataclasses as _dc

            from deepspeedsyclsupport_tpu.inference.v2 import (
                load_journal, recover_requests)

            crashed = True
            eng.flush(list(eng.seqs))   # KV state + descriptors lost
            # the dead incarnation's trace ring survives the crash (it is
            # host memory, like the journal is disk) — bank it for the
            # point's request waterfall before the session goes away
            trace_records.extend(sess.drain_trace())
            sess.close()
            states, last_t = load_journal(journal_dir)
            sess = ServingSession(
                eng, _dc.replace(pol, journal_path=journal_path(
                    journal_dir, attempt=1)),
                capacity=capacity)
            recovery_summary = recover_requests(sess, states, last_t)
            now = time.perf_counter()
            for uid in recovery_summary["shed"]:
                # a replay shed is terminal without a session event —
                # account it as an SLA miss like any other shed
                shed += 1
                record_done(uid, now, was_evicted=True)
            continue
        if events:
            stall_guard = 0
            continue
        if sess.idle and due:
            wake = min(w for w, _u, _c in due)
            if deadline is not None:
                wake = min(wake, deadline)
            time.sleep(max(0.0, wake - time.perf_counter()))
            stall_guard = 0
            continue
        stall_guard += 1
        if stall_guard > 200:
            raise RuntimeError(
                f"sla serving loop stalled: {sess.stats()}, "
                f"{finished}/{total} done")
    wall = time.perf_counter() - t0
    res = _serving_result(wall, total, evicted, total_decoded,
                          evicted_tokens, ttfts, itls,
                          getattr(eng, "host_dispatches", 0) - dispatches0,
                          req_stats)
    st = sess.stats()
    res["serve"] = {"admitted": st["admitted"], "queued": st["queued"],
                    "shed": shed, "evicted": st["evicted"],
                    "shed_pct": round(100.0 * shed / max(total, 1), 1),
                    "prefill_tok_s_est": st["prefill_tok_s_est"],
                    "decode_step_s_est": st["decode_step_s_est"]}
    if recovery_summary is not None:
        res["serve"]["recovery"] = {
            "replays": len(recovery_summary["replayed"]),
            "replay_sheds": len(recovery_summary["shed"]),
            "time_to_recover_s": recovery_summary["time_to_recover_s"]}
    trace_records.extend(sess.drain_trace())
    res["trace"] = trace_records
    if journal_dir is not None:
        sess.close()
    return res


def _serve_once(model_name, platform, *, n_clients, reqs_per_client,
                prompt_len, gen_len, budget, block_size, max_context,
                attn=None, scenario_budget_s=None):
    import jax

    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    cfg = get_config(model_name, max_seq_len=max_context)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    max_seqs = max(8, 2 * n_clients)
    extra = _attn_overrides(attn)
    eng = InferenceEngineV2(model, params,
                            config={"max_tokens_per_batch": budget,
                                    "block_size": block_size,
                                    "max_context": max_context,
                                    "max_sequences": max_seqs,
                                    # fully-committed KV pool: a decode
                                    # token can never be rejected, so the
                                    # driver's eviction path stays cold
                                    "num_blocks": max_seqs
                                    * (max_context // block_size),
                                    **extra})
    import numpy as np

    rng = np.random.RandomState(0)

    def mk_prompt():
        return [int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                            size=prompt_len)]

    # compile prefill + decode in both KV-sharding states outside the
    # timed window (engine-owned warmup; see InferenceEngineV2.warmup)
    eng.warmup()

    results = {}
    for i, mode in enumerate(("naive", "splitfuse")):
        uid_base = (i + 1) * 1_000_000
        prompts = {}
        for c in range(n_clients):
            for r in range(reqs_per_client):
                prompts[uid_base + c * 1000 + r] = mk_prompt()
        deadline = (time.perf_counter() + scenario_budget_s
                    if scenario_budget_s else None)
        results[mode] = _drive_serving(eng, prompts, n_clients,
                                       reqs_per_client, gen_len, mode,
                                       uid_base, deadline=deadline)
        results[mode].pop("req_stats", None)  # per-request rows are
        # goodput-rung fuel, not serve-line payload
        # flush the completed arm NOW: if the other arm hangs/overruns,
        # the parent's partial-stdout parse still banks this measurement
        _emit({"metric": f"serve_arm_{mode}_{model_name}",
               "value": results[mode]["throughput_tok_s"],
               "unit": "tokens/s", "vs_baseline": 0.0,
               "detail": {"platform": platform, "partial": True,
                          "mode": mode, "clients": n_clients,
                          **results[mode]}})
    speedup = (results["splitfuse"]["throughput_tok_s"]
               / max(results["naive"]["throughput_tok_s"], 1e-9))
    sf = results["splitfuse"]
    return {
        "metric": f"serve_decode_tok_per_sec_per_chip_{model_name}",
        "value": sf["throughput_tok_s"],
        "unit": "tokens/s",
        "vs_baseline": round(speedup / REFERENCE_FASTGEN_SPEEDUP, 4),
        "detail": {"platform": platform, "model": model_name,
                   "clients": n_clients, "prompt_len": prompt_len,
                   "gen_len": gen_len, "token_budget": budget,
                   "attn_impl": attn or "auto",
                   "ttft_p50_s": sf["ttft_p50_s"],
                   "ttft_p95_s": sf["ttft_p95_s"],
                   "itl_p95_s": sf["itl_p95_s"],
                   "splitfuse_vs_naive_speedup": round(speedup, 3),
                   "naive": results["naive"], "splitfuse": sf,
                   "baseline": "SplitFuse-vs-naive effective-throughput "
                               "ratio vs the reference FastGen 2.3x "
                               "headline"},
    }


# ==================================================================
# rung: serve_goodput (the reference's ACTUAL headline metric — goodput
# under a per-client token-rate SLA across a load sweep;
# blogs/deepspeed-fastgen/README.md:28,139-177)
# ==================================================================
def _goodput(req_stats, sla_rate, ttft_sla, wall):
    """FastGen-style two-part SLA per request: first token within
    ``ttft_sla`` AND decode rate (tokens per second after the first token,
    queue time excluded) at least ``sla_rate``. Returns
    (goodput tokens/s, sla_miss_fraction)."""
    met_tokens = 0
    missed = 0
    for t_sub, t_done, toks, was_evicted, ttft in req_stats:
        decode_dur = max(t_done - t_sub - ttft, 1e-9)
        rate_ok = toks > 1 and (toks - 1) / decode_dur >= sla_rate
        if (not was_evicted) and ttft <= ttft_sla and rate_ok:
            met_tokens += toks
        else:
            missed += 1
    n = max(len(req_stats), 1)
    return met_tokens / max(wall, 1e-9), missed / n


def _serve_goodput_once(model_name, platform, *, client_sweep,
                        reqs_per_client, prompt_len, gen_len, budget,
                        block_size, max_context, attn=None,
                        sweep_budget_s=None):
    """Load sweep: closed-loop clients at increasing counts; SLA is a
    per-client token rate calibrated to 50% of the solo (1-client) decode
    rate — the blog's 'effective throughput under a latency SLA' shape.
    SplitFuse and naive run the SAME work at each load point.

    Per-scenario timeout (the r05 rc=124 fix): each completed load point is
    flushed as a partial JSON line the moment it finishes, every arm runs
    under a deadline carved from ``sweep_budget_s``, and a timed-out arm
    ends the sweep with the completed points reported — a sweep that dies
    at 10 clients still banks the 4- and 6-client measurements."""
    import jax
    import numpy as np

    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    cfg = get_config(model_name, max_seq_len=max_context)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # engine capacity is deliberately CAPPED below the heaviest sweep point:
    # beyond-capacity load points (25/50 clients on CPU, 64 on TPU) are
    # exactly the overload the admission gate must degrade gracefully under
    # — and the padded forwards' per-step cost stays constant across the
    # sweep, so light-load points are not taxed for the heavy ones
    max_seqs = max(8, 2 * min(max(client_sweep),
                              16 if platform == "tpu" else 10))
    extra = _attn_overrides(attn)
    eng = InferenceEngineV2(model, params,
                            config={"max_tokens_per_batch": budget,
                                    "block_size": block_size,
                                    "max_context": max_context,
                                    "max_sequences": max_seqs,
                                    "num_blocks": max_seqs
                                    * (max_context // block_size),
                                    # SLA arm levers: fused K-step decode at
                                    # the pre-seed K-sweep knee + slack-based
                                    # KV eviction. max_prefill_fraction stays
                                    # 1.0: on the CPU sim the fraction only
                                    # SPREADS a prompt's fixed compute across
                                    # more mixed forwards (same total decode
                                    # stall, more dispatches) — admission is
                                    # the overload valve, not chunk shrinking
                                    "decode_steps_per_dispatch": 16,
                                    "eviction_policy": "slack",
                                    **extra})
    rng = np.random.RandomState(0)

    def prompts_for(uid_base, n_clients, reqs=None):
        return {uid_base + c * 1000 + r:
                [int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                             size=prompt_len)]
                for c in range(n_clients)
                for r in range(reqs or reqs_per_client)}

    eng.warmup(fused_ladder=True)  # pre-compile every fused-K rung: a tail
    # absorbing < K steps mid-sweep must not pay a compile inside a timed arm
    # ONE deadline covers calibration + sweep: the budget bounds the whole
    # call, not each phase separately
    sweep_end = (time.perf_counter() + sweep_budget_s
                 if sweep_budget_s else None)
    # SLA calibration: solo client, PER-TOKEN splitfuse arm — median ITL
    # sets the unloaded decode rate (SLA demands half of it, queue
    # excluded), solo TTFT sets the first-token bound (SLA allows 5x:
    # queueing headroom, the blog's latency-SLA shape). Per-token on
    # purpose, twice over: it keeps the SLA thresholds comparable with the
    # r05 baseline, and the fused-amortized solo ITL is 2-3x faster than
    # any sustainable loaded step time — calibrating off it would demand a
    # rate even graceful shedding cannot meet
    solo = _drive_serving(eng, prompts_for(9_000_000, 1), 1, 1,
                          gen_len, "splitfuse", 9_000_000,
                          deadline=sweep_end)
    solo.pop("req_stats", None)
    # seed the sweep-shared capacity model from the solo measurements so
    # the first load point's admission gate projects from data, not priors
    from deepspeedsyclsupport_tpu.inference.v2 import CapacityModel
    capacity = CapacityModel()
    capacity.record_prefill(prompt_len, max(solo["ttft_p50_s"], 1e-6))
    capacity.record_decode(1, max(solo["itl_p50_s"], 1e-6))
    solo_rate = 1.0 / max(solo["itl_p50_s"], 1e-6)
    sla_rate = 0.5 * solo_rate
    # TTFT bound stays loose (5x solo): the discriminating bound is the
    # decode rate — naive's prefill-preemption stalls every live decode,
    # which is exactly the behavior the blog's consistency curves indict
    ttft_sla = 5.0 * max(solo["ttft_p50_s"], 1e-3)

    # staggered first arrivals: clients spread over one solo request span,
    # so prompts land WHILE earlier clients decode (the blog's steady-state
    # mix); later requests are closed-loop
    solo_span = solo["ttft_p50_s"] + gen_len * solo["itl_p50_s"]

    points = []
    skipped = []
    best = None
    for li, n_clients in enumerate(client_sweep):
        point = {"clients": n_clients, "sla_tok_s": round(sla_rate, 2),
                 "sla_ttft_s": round(ttft_sla, 3)}
        timed_out = None
        for mi, mode in enumerate(("naive", "splitfuse")):
            if sweep_end is not None and time.perf_counter() > sweep_end:
                timed_out = f"{mode}: sweep budget exhausted before start"
                break
            uid_base = (li * 2 + mi + 1) * 1_000_000
            arrivals = {uid_base + c * 1000 + 0: c * solo_span / n_clients
                        for c in range(n_clients)}
            try:
                if mode == "splitfuse":
                    # the SplitFuse arm runs the full SLA policy layer:
                    # admission (queue/shed vs the calibrated SLA), slack
                    # scheduling, preemption, fused decode
                    r = _drive_serving_sla(
                        eng, prompts_for(uid_base, n_clients), n_clients,
                        reqs_per_client, gen_len, uid_base,
                        arrival_of=arrivals, deadline=sweep_end,
                        ttft_sla=ttft_sla, rate_sla=sla_rate,
                        capacity=capacity)
                else:
                    r = _drive_serving(eng, prompts_for(uid_base, n_clients),
                                       n_clients, reqs_per_client, gen_len,
                                       mode, uid_base, arrival_of=arrivals,
                                       deadline=sweep_end)
            except _ScenarioTimeout as e:
                timed_out = str(e)
                break
            gp, miss = _goodput(r.pop("req_stats"), sla_rate, ttft_sla,
                                r["wall_s"])
            if mode == "splitfuse":
                # per-load-point request-time attribution off the SLA
                # arm's in-memory trace ring (no disk IO in the timed path)
                point["request_waterfall"] = _request_waterfall(
                    [("0", r.pop("trace", []))])
            point[mode] = {"goodput_tok_s": round(gp, 2),
                           "sla_miss_pct": round(100 * miss, 1),
                           "shed_pct": r.get("serve", {}).get("shed_pct",
                                                              0.0),
                           "throughput_tok_s": r["throughput_tok_s"],
                           "ttft_p50_s": r["ttft_p50_s"],
                           "ttft_p95_s": r["ttft_p95_s"],
                           "itl_p50_s": r["itl_p50_s"],
                           "itl_p95_s": r["itl_p95_s"],
                           "itl_std_s": r["itl_std_s"],
                           "host_dispatches_per_token":
                               r["host_dispatches_per_token"],
                           **({"serve": r["serve"]} if "serve" in r else {})}
        if timed_out is not None:
            # the remaining (heavier) load points would also overrun:
            # stop the sweep, keep what completed
            skipped.append({"clients": n_clients, "reason": timed_out})
            skipped.extend({"clients": c, "reason": "after timeout"}
                           for c in client_sweep[li + 1:])
            print(f"serve_goodput: load point {n_clients} timed out "
                  f"({timed_out}); reporting {len(points)} completed "
                  f"point(s)", file=sys.stderr)
            break
        ratio = (point["splitfuse"]["goodput_tok_s"]
                 / max(point["naive"]["goodput_tok_s"], 1e-9))
        if point["naive"]["goodput_tok_s"] <= 0 and ratio > 100.0:
            # naive collapsed to zero goodput (the r05 overload signature):
            # any survivor makes the raw ratio unbounded — cap it so the
            # headline reads "graceful vs collapsed", not a fake 1e10x
            ratio = 100.0
            point["naive_collapsed"] = True
        point["goodput_ratio"] = round(ratio, 3)
        points.append(point)
        # flush the completed point NOW (partial line): a later kill —
        # SIGTERM, rc=124, a hung arm — cannot take it back
        _emit({"metric": f"serve_goodput_point_{model_name}",
               "value": point["splitfuse"]["goodput_tok_s"],
               "unit": "tokens/s", "vs_baseline": 0.0,
               "detail": {"platform": platform, "partial": True,
                          "point": point}})
        if best is None or ratio > best[1]:
            best = (n_clients, ratio, point)

    if best is None:
        raise RuntimeError(
            f"serve_goodput: no load point completed inside the sweep "
            f"budget ({sweep_budget_s}s); skipped={skipped}")

    # ------- availability detail: goodput THROUGH a fault. One extra run
    # of a completed load point with an injected mid-decode replica death
    # + journal-replay failover (inference/v2/supervisor.py), compared
    # against the SAME load's fault-free SLA arm from the sweep. The
    # pre-journal/pre-replay behavior was total loss of every in-flight
    # stream — the contract here is nonzero goodput through the fault.
    availability = None
    if points and (sweep_end is None
                   or sweep_end - time.perf_counter() > 60):
        import tempfile

        # lightest COMPLETED point (least fault-free shedding), with
        # enough requests per client that some are served entirely before
        # or after the fault — the streams live at the crash instant eat
        # the recovery gap in their decode rate (an honest SLA miss), so
        # the surviving goodput comes from the rest
        n_av = points[0]["clients"]
        av_reqs = max(3, reqs_per_client)
        uid_base = 17_000_000
        arrivals = {uid_base + c * 1000 + 0: c * solo_span / n_av
                    for c in range(n_av)}
        crash_tokens = max(8, n_av * av_reqs * gen_len // 4)
        try:
            with tempfile.TemporaryDirectory() as jdir:
                # fault-free arm at the SAME load shape (reqs differ from
                # the sweep point, so re-measure rather than reuse)
                ff_r = _drive_serving_sla(
                    eng, prompts_for(uid_base + 500, n_av, av_reqs),
                    n_av, av_reqs,
                    gen_len, uid_base + 500, arrival_of={
                        uid_base + 500 + c * 1000: c * solo_span / n_av
                        for c in range(n_av)},
                    deadline=sweep_end, ttft_sla=ttft_sla,
                    rate_sla=sla_rate, capacity=capacity)
                ff_gp, _ = _goodput(ff_r.pop("req_stats"), sla_rate,
                                    ttft_sla, ff_r["wall_s"])
                ff_r.pop("trace", None)
                r = _drive_serving_sla(
                    eng, prompts_for(uid_base, n_av, av_reqs),
                    n_av, av_reqs,
                    gen_len, uid_base,
                    arrival_of=arrivals, deadline=sweep_end,
                    ttft_sla=ttft_sla, rate_sla=sla_rate,
                    capacity=capacity, journal_dir=jdir,
                    crash_at_tokens=crash_tokens)
            gp, miss = _goodput(r.pop("req_stats"), sla_rate, ttft_sla,
                                r["wall_s"])
            availability = {
                "clients": n_av, "reqs_per_client": av_reqs,
                "crash_at_tokens": crash_tokens,
                # the trace spans BOTH incarnations: replay segments and
                # requeue waits show up as their own stages
                "request_waterfall": _request_waterfall(
                    [("0", r.pop("trace", []))]),
                "goodput_fault_free": round(ff_gp, 2),
                "goodput_with_recovery": round(gp, 2),
                "availability_ratio": round(gp / max(ff_gp, 1e-9), 3),
                "sla_miss_pct": round(100 * miss, 1),
                "recovery": r["serve"].get("recovery", {}),
                "baseline": "same-load fault-free SLA arm (availability "
                            "phase)"}
        except Exception as e:  # availability is a detail, never the rung
            availability = {"clients": n_av, "error": str(e)[:200]}

    return {
        "metric": f"serve_goodput_sla_{model_name}",
        "value": best[2]["splitfuse"]["goodput_tok_s"],
        "unit": "tokens/s",
        "vs_baseline": round(best[1] / REFERENCE_FASTGEN_SPEEDUP, 4),
        "detail": {"platform": platform, "model": model_name,
                   "prompt_len": prompt_len, "gen_len": gen_len,
                   "token_budget": budget,
                   "attn_impl": attn or "auto",
                   "sla": "per-request: TTFT <= 5x solo TTFT AND decode "
                          "rate (post-first-token) >= 50% of solo rate",
                   "best_load_point_clients": best[0],
                   "best_goodput_ratio_splitfuse_vs_naive": round(best[1], 3),
                   "load_sweep": points,
                   "load_points_skipped": skipped,
                   "availability": availability,
                   "baseline": "SplitFuse-vs-naive goodput ratio at the "
                               "best load point vs the reference FastGen "
                               "2.3x effective-throughput headline"},
    }


def run_serve_goodput():
    jax = _child_jax()

    platform = jax.devices()[0].platform
    if platform == "tpu":
        # sweeps extend past engine capacity (max_sequences caps at 2x16):
        # the 64-client point is pure overload — the admission gate's
        # graceful-shedding territory
        ladder = [
            dict(model_name="llama-650m", client_sweep=[4, 16, 32, 64],
                 reqs_per_client=2, prompt_len=512, gen_len=64, budget=256,
                 block_size=64, max_context=1024),
            # XLA fallback if the Pallas serving path trips remote Mosaic
            dict(model_name="llama-650m", client_sweep=[4, 16, 32, 64],
                 reqs_per_client=2, prompt_len=512, gen_len=64, budget=256,
                 block_size=64, max_context=1024, attn="xla"),
            dict(model_name="tiny", client_sweep=[4, 16, 32, 64],
                 reqs_per_client=2, prompt_len=512, gen_len=64, budget=256,
                 block_size=64, max_context=1024),
        ]
    else:
        # budget « prompt so chunking matters (VERDICT r4 #3), scaled to
        # what the CPU sim finishes inside the rung timeout; 25/50 clients
        # run 1.25x/2.5x past the engine's 20-slot capacity — the fleet-
        # scale overload points where shed_pct > 0 is the CORRECT outcome
        # NOTE on CPU-sim fidelity: a forward's wall time here scales
        # ~linearly with its token count, so a chunk-carrying fused forward
        # pays ~budget/decode-tokens more than a pure-decode forward — on
        # TPU at these sizes both are launch/HBM-bound and nearly equal,
        # which is the effect the SplitFuse headline rides. The CPU number
        # is therefore a structural UNDERestimate of the TPU ratio.
        ladder = [
            dict(model_name="tiny", client_sweep=[2, 6, 10, 25, 50],
                 reqs_per_client=1, prompt_len=512, gen_len=64, budget=96,
                 block_size=32, max_context=1024),
        ]
    # ONE budget for the whole rung, carved across ladder retries (see
    # run_serve); each config's sweep gets what the earlier ones left
    rung_end = time.monotonic() + float(
        os.environ.get("DSTPU_GOODPUT_SWEEP_BUDGET", 540))
    last_err = None
    for cfg in ladder:
        remaining = rung_end - time.monotonic()
        if remaining < 60:
            last_err = f"{cfg['model_name']}: skipped (rung budget)"
            break
        try:
            _emit(_serve_goodput_once(platform=platform,
                                      sweep_budget_s=remaining, **cfg))
            return
        except Exception as e:
            last_err = (f"{cfg['model_name']}[{cfg.get('attn') or 'auto'}]: "
                        f"{str(e)[:300]}")
            print(f"serve_goodput rung failed: {last_err}", file=sys.stderr)
            jax.clear_caches()
    raise RuntimeError(f"all serve_goodput rungs failed; last: {last_err}")


# ==================================================================
# rung: fleet (serving fleet control plane — routed goodput THROUGH a
# mid-sweep replica kill; inference/v2/fleet, docs/serving.md)
# ==================================================================
def _drive_fleet(router, replicas, prompts, n_clients, reqs_per_client,
                 gen_len, uid_base, arrival_of=None, deadline=None,
                 ttft_sla=None, rate_sla=None, kill_at_tokens=None,
                 kill_replica=None):
    """Closed-loop clients over the fleet router (in-process
    ``LocalReplica`` endpoints — the CPU-sim fleet). Same shape as
    ``_drive_serving_sla`` one level up: the router owns edge admission,
    placement and failover; this loop owns client pacing and delivery.

    ``kill_at_tokens`` + ``kill_replica`` inject the mid-sweep replica
    death: once that many tokens have been delivered fleet-wide, the
    replica dies hard (KV + session state dropped, journal left open) and
    the router's next poll claims its journaled in-flight streams and
    re-admits them on the survivors. The wall clock runs through the
    failover — goodput-through-fault includes the recovery gap honestly.

    Runs on wall clock (``time.time``): fleet observations join
    cross-process timestamps by contract, and the CPU-sim fleet keeps the
    same convention so the numbers compare."""
    from deepspeedsyclsupport_tpu.inference.v2.fleet import FleetRequest

    arrival_of = arrival_of or {}
    killed = kill_at_tokens is None
    total = n_clients * reqs_per_client
    submitted, gen_count, ttft_of, last_tok, client_of = {}, {}, {}, {}, {}
    next_req = [0] * n_clients
    finished = shed = evicted = evicted_tokens = total_decoded = 0
    req_stats = []
    due = []
    ttfts, itls = [], []
    failover_info = None
    # per-POINT breakdown: the router's ledgers are cumulative across the
    # sweep (one fleet, many load points) — delta them
    pr0 = {rid: dict(c) for rid, c in router.per_replica.items()}
    t0 = time.time()

    def queue_next(c, when):
        i = next_req[c]
        next_req[c] += 1
        uid = uid_base + c * 1000 + i
        due.append((when, uid, c))
        client_of[uid] = c

    def record_done(uid, now, was_evicted):
        nonlocal finished
        finished += 1
        req_stats.append((submitted[uid], now, gen_count.get(uid, 0),
                          was_evicted, ttft_of.get(uid, 0.0)))
        c = client_of[uid]
        if next_req[c] < reqs_per_client:
            queue_next(c, now)

    for c in range(n_clients):
        queue_next(c, t0 + arrival_of.get(uid_base + c * 1000 + 0, 0.0))

    stall_guard = 0
    while finished < total:
        now = time.time()
        if deadline is not None and now > deadline:
            raise _ScenarioTimeout(
                f"fleet: scenario deadline after {finished}/{total} "
                f"requests ({total_decoded} tokens, {shed} shed)")
        for when, uid, c in [d for d in due if d[0] <= now]:
            due.remove((when, uid, c))
            submitted[uid] = max(now, when)
            gen_count[uid] = 0
            outcome, _rid = router.submit(FleetRequest(
                uid=uid, tokens=prompts[uid], max_new_tokens=gen_len,
                tenant=f"client{c % 8}", ttft_sla_s=ttft_sla,
                rate_sla=rate_sla or 0.0), now=now)
            if outcome == "shed":
                shed += 1
                record_done(uid, now, was_evicted=True)
        events = router.poll(now=now)
        for ev in events:
            if ev.kind == "token":
                uid = ev.uid
                n = len(ev.tokens)
                if uid not in ttft_of:
                    ttft_of[uid] = ev.t - submitted[uid]
                    ttfts.append(ttft_of[uid])
                else:
                    itls.extend([(ev.t - last_tok[uid]) / n] * n)
                last_tok[uid] = ev.t
                gen_count[uid] += n
                total_decoded += n
            elif ev.kind == "finish":
                was_evicted = ev.reason == "evicted"
                if was_evicted:
                    evicted += 1
                    evicted_tokens += gen_count.get(ev.uid, 0)
                record_done(ev.uid, ev.t, was_evicted)
            elif ev.kind == "shed":
                shed += 1
                record_done(ev.uid, ev.t, was_evicted=True)
        if not killed and total_decoded >= kill_at_tokens:
            killed = True
            replicas[kill_replica].kill()
            # the NEXT poll observes the death and fails over (its events
            # flow through the normal delivery path above)
            failover_info = {
                "killed_replica": kill_replica,
                "at_tokens": total_decoded,
                "counters_before": dict(router.failover_counters)}
            continue
        if events:
            stall_guard = 0
            continue
        if router.idle and due:
            wake = min(w for w, _u, _c in due)
            if deadline is not None:
                wake = min(wake, deadline)
            time.sleep(max(0.0, wake - time.time()))
            stall_guard = 0
            continue
        stall_guard += 1
        if stall_guard > 500:
            raise RuntimeError(
                f"fleet loop stalled: {router.stats()}, "
                f"{finished}/{total} done")
    wall = time.time() - t0
    res = _serving_result(wall, total, evicted, total_decoded,
                          evicted_tokens, ttfts, itls, 0, req_stats)
    res.pop("host_dispatches", None)
    res.pop("host_dispatches_per_token", None)
    res["fleet"] = router.stats()
    res["fleet"]["point_shed"] = shed
    res["fleet"]["point_per_replica"] = {
        rid: {k: c[k] - pr0[rid].get(k, 0) for k in c}
        for rid, c in router.per_replica.items()}
    if failover_info is not None:
        before = failover_info.pop("counters_before")
        failover_info.update(
            {k: v - before.get(k, 0)
             for k, v in router.failover_counters.items()})
        res["fleet"]["failover"] = failover_info
    return res


def run_fleet():
    """2–4 replica CPU-sim fleet under 100+ concurrent clients with a
    mid-sweep replica kill: the headline is fleet goodput THROUGH the
    fault — nonzero, shed-accounted degradation instead of collapse.
    Every completed load point flushes as a partial JSON line (the same
    salvage contract as the serving sweeps) so an outer timeout still
    measures completed points."""
    jax = _child_jax()
    import shutil
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from deepspeedsyclsupport_tpu.inference.v2 import (InferenceEngineV2,
                                                       ServingPolicyConfig,
                                                       ServingSession)
    from deepspeedsyclsupport_tpu.inference.v2.fleet import (FleetConfig,
                                                             FleetRouter,
                                                             LocalReplica)
    from deepspeedsyclsupport_tpu.inference.v2.supervisor import journal_path
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    platform = jax.devices()[0].platform
    n_replicas = int(os.environ.get("DSTPU_FLEET_REPLICAS", "3"))
    prompt_len, gen_len, reqs_per_client = 48, 16, 2
    max_seqs = 16
    # 12 = light (fleet capacity is 3x16 slots), 48 = at capacity,
    # 120 = pure overload — the edge gate's graceful-shedding territory
    client_sweep = [12, 48, 120]
    sweep_budget_s = float(os.environ.get("DSTPU_FLEET_SWEEP_BUDGET", 420))
    cfg = get_config("tiny", max_seq_len=256)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)

    # shared per-tenant prompt HEADS (2 full blocks): clients of one
    # tenant open with the same system text — the workload shape the
    # tenant-affinity router co-locates and the replicas' prefix caches
    # convert into skipped prefill (stats()["realized_reuse"] is the join)
    head_len = 32
    tenant_heads = {g: [int(t) for t in rng.randint(
        1, cfg.vocab_size - 1, size=head_len)] for g in range(8)}

    def prompts_for(uid_base, n_clients):
        return {uid_base + c * 1000 + r:
                tenant_heads[c % 8]
                + [int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                               size=prompt_len - head_len)]
                for c in range(n_clients) for r in range(reqs_per_client)}

    root = tempfile.mkdtemp(prefix="dstpu_bench_fleet_")
    sessions = []

    def mk_engine():
        eng = InferenceEngineV2(
            model, params, dtype=jnp.bfloat16,
            config={"block_size": 16, "max_context": 256,
                    "max_tokens_per_batch": 96, "max_sequences": max_seqs,
                    "num_blocks": max_seqs * (256 // 16),
                    "decode_steps_per_dispatch": 8,
                    "eviction_policy": "slack"})
        eng.warmup(fused_ladder=True)
        return eng

    engines = [mk_engine() for _ in range(n_replicas)]
    deadline = time.time() + sweep_budget_s

    # SLA calibration: solo client, PER-TOKEN drive on one engine — the
    # fused-amortized solo ITL is far faster than any sustainable loaded
    # step time, so calibrating off it would demand a rate even graceful
    # shedding cannot meet (the serve_goodput calibration rule, verbatim)
    solo = _drive_serving(engines[0], prompts_for(9_000_000, 1), 1, 1,
                          gen_len, "splitfuse", 9_000_000)
    # looser factors than the single-replica serve_goodput SLA (5x TTFT /
    # 0.5x rate): on the CPU sim a mixed prefill+decode forward's wall
    # time scales with its token count, so a loaded fleet's per-stream
    # rate sits several x below the solo per-token rate by construction
    # (the serve_goodput NOTE on CPU-sim fidelity) — on TPU both are
    # launch/HBM-bound and the tighter factors would be the right call
    sla_rate = 0.25 / max(solo["itl_p50_s"], 1e-6)
    ttft_sla = 10.0 * max(solo["ttft_p50_s"], 1e-3)
    solo_span = solo["ttft_p50_s"] + gen_len * solo["itl_p50_s"]

    def mk_replica(rid):
        jdir = os.path.join(root, f"replica{rid}", "journal")
        os.makedirs(jdir, exist_ok=True)
        # replica sessions are structural-only (admission "none": queue on
        # engine limits) — SLA admission lives at the FLEET EDGE, in the
        # router, so hopeless requests shed before any replica queues
        sess = ServingSession(engines[int(rid)], ServingPolicyConfig(
            admission="none", journal_path=journal_path(jdir),
            prefix_cache={"enabled": True}))
        sessions.append(sess)
        return LocalReplica(str(rid), sess, journal_dir=jdir)

    replicas = {str(i): mk_replica(i) for i in range(n_replicas)}
    router = FleetRouter(
        list(replicas.values()),
        FleetConfig(affinity="tenant",
                    log_path=os.path.join(root, "router.jsonl")))
    # seed EVERY replica's router-side capacity model from the solo
    # measurements: the edge gate must project from data, not priors, for
    # replicas that have not served yet (the serve_goodput seeding rule)
    for cap in router.caps.values():
        cap.record_prefill(prompt_len, max(solo["ttft_p50_s"], 1e-6))
        cap.record_decode(1, max(solo["itl_p50_s"], 1e-6))
    points, skipped = [], []
    kill_done = False
    try:
        for li, n_clients in enumerate(client_sweep):
            if time.time() > deadline - 30:
                skipped.append({"clients": n_clients,
                                "reason": "sweep budget exhausted"})
                continue
            uid_base = (li + 1) * 1_000_000
            # paced arrivals: ~8 new clients per solo request span — a
            # sustained offered load, not one burst the first point's
            # still-calibrating capacity model cannot project
            arrivals = {uid_base + c * 1000 + 0: c * solo_span / 8.0
                        for c in range(n_clients)}
            # the mid-sweep kill lands in the HEAVIEST load point: fleet
            # goodput through the fault is the headline. The threshold is
            # sized to the fleet's live set (not offered load — overload
            # sheds most of that), so it fires mid-decode of the first
            # admitted wave.
            inject = (not kill_done and n_clients == max(client_sweep))
            try:
                r = _drive_fleet(
                    router, replicas, prompts_for(uid_base, n_clients),
                    n_clients, reqs_per_client, gen_len, uid_base,
                    arrival_of=arrivals, deadline=deadline,
                    ttft_sla=ttft_sla, rate_sla=sla_rate,
                    kill_at_tokens=(max_seqs * gen_len // 2 if inject
                                    else None),
                    kill_replica=("0" if inject else None))
            except _ScenarioTimeout as e:
                skipped.append({"clients": n_clients, "reason": str(e)})
                skipped.extend({"clients": c, "reason": "after timeout"}
                               for c in client_sweep[li + 1:])
                break
            if inject:
                kill_done = True
            gp, miss = _goodput(r.pop("req_stats"), sla_rate, ttft_sla,
                                r["wall_s"])
            fl = r["fleet"]
            point = {
                "clients": n_clients,
                "goodput_tok_s": round(gp, 2),
                "sla_miss_pct": round(100 * miss, 1),
                "shed_pct": round(100.0 * fl["point_shed"]
                                  / max(n_clients * reqs_per_client, 1), 1),
                "throughput_tok_s": r["throughput_tok_s"],
                "ttft_p50_s": r["ttft_p50_s"],
                "ttft_p95_s": r["ttft_p95_s"],
                "itl_p50_s": r["itl_p50_s"],
                "replicas_ready": fl["replicas_ready"],
                "replica_kill": fl.get("failover"),
                "per_replica": fl["point_per_replica"],
                # placement-side affinity joined with engine-reported
                # prefix reuse (cumulative across the sweep)
                "realized_reuse": {
                    k: v for k, v in (fl.get("realized_reuse") or {}).items()
                    if k != "per_replica"},
                # fleet-wide request waterfall for THIS point: every
                # replica's trace ring (the killed one's ring survives the
                # kill — host memory, like its journal survives on disk)
                # joined with the router's stream on one wall-clock base
                "request_waterfall": _request_waterfall(
                    [(rid, rep.session.drain_trace())
                     for rid, rep in replicas.items()],
                    router_records=router.drain_trace()),
            }
            points.append(point)
            # flush NOW: a later kill cannot take the completed point back
            _emit({"metric": "fleet_goodput_point_tiny",
                   "value": point["goodput_tok_s"], "unit": "tokens/s",
                   "vs_baseline": 0.0,
                   "detail": {"platform": platform, "partial": True,
                              "n_replicas": n_replicas, "point": point}})
    finally:
        router.close()
        for sess in sessions:
            try:
                sess.close()
            except Exception:
                pass
        shutil.rmtree(root, ignore_errors=True)
    if not points:
        raise RuntimeError(f"fleet: no load point completed; "
                           f"skipped={skipped}")
    fault_points = [p for p in points if p.get("replica_kill")]
    head = fault_points[-1] if fault_points else points[-1]
    _emit({
        "metric": "fleet_goodput_tiny",
        "value": head["goodput_tok_s"],
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": {
            "platform": platform, "model": "tiny",
            "n_replicas": n_replicas,
            "clients_at_headline": head["clients"],
            "sla": "per-request: TTFT <= 10x solo TTFT AND decode rate >= "
                   "25% of solo per-token rate (looser than serve_goodput's "
                   "5x/50%: the CPU sim's mixed-forward cost scales with "
                   "token count, structurally depressing loaded rates)",
            "sla_tok_s": round(sla_rate, 2),
            "sla_ttft_s": round(ttft_sla, 3),
            "headline": "fleet goodput THROUGH a mid-sweep replica kill "
                        "(nonzero + shed-accounted degradation, no "
                        "collapse)",
            "goodput_through_fault_nonzero": bool(
                head["goodput_tok_s"] > 0),
            "realized_reuse": head.get("realized_reuse"),
            "load_sweep": points,
            "load_points_skipped": skipped,
        }})


# ==================================================================
# rung: serve_prefix (cross-request KV prefix cache A/B — shared system
# prompt served cache-on vs cache-off; inference/v2/prefix_cache.py,
# docs/serving.md "prefix reuse")
# ==================================================================
def _drive_prefix_arm(eng, prefix_cache, prompts, gen_len, deadline=None):
    """Submit every request up-front (the queue absorbs the overflow —
    queue wait is part of TTFT, which is exactly what cached prefill
    shortens), drive the session to idle, return per-uid outputs + TTFT.
    Greedy sampling: outputs are a pure function of the prompt, the
    byte-identity oracle between the arms."""
    from deepspeedsyclsupport_tpu.inference.v2 import (ServingPolicyConfig,
                                                       ServingSession)

    sess = ServingSession(eng, ServingPolicyConfig(
        admission="none", shed_policy="queue", preempt_policy="requeue",
        prefix_cache=prefix_cache))
    outs, ttft, submitted = {}, {}, {}
    t0 = time.perf_counter()
    for uid in sorted(prompts):
        submitted[uid] = time.perf_counter()
        sess.submit(uid, prompts[uid], gen_len)
    steps = 0
    while not sess.idle:
        if deadline is not None and time.perf_counter() > deadline:
            raise _ScenarioTimeout(
                f"serve_prefix: arm deadline after {len(outs)}/"
                f"{len(prompts)} streams started")
        for ev in sess.step():
            if ev.kind == "token":
                if ev.uid not in ttft:
                    ttft[ev.uid] = ev.t - submitted[ev.uid]
                outs.setdefault(ev.uid, []).extend(ev.tokens)
        steps += 1
        if steps > 50_000:
            raise RuntimeError(f"serve_prefix arm stalled: {sess.stats()}")
    return {"outs": outs, "ttft": ttft,
            "wall_s": time.perf_counter() - t0,
            "serve": sess.stats(), "prefix": sess.prefix_stats(),
            "trace": sess.drain_trace()}


def _serve_prefix_once(model_name, platform, *, load_sweep, system_len,
                       tail_len, gen_len, budget, block_size, max_context,
                       attn=None, sweep_budget_s=None):
    """Shared-system-prompt workload (every request = system prompt +
    unique tail, the RAG/agent shape) served twice per load point on ONE
    warm engine: cache-off then cache-on. The contract: byte-identical
    outputs, hit ratio > 0.5 once the first wave has committed the system
    blocks, and lower mean TTFT (the cached arm's prefill is a block-table
    copy + the novel tail)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    assert system_len % block_size == 0, "system prompt must be full blocks"
    cfg = get_config(model_name, max_seq_len=max_context)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    max_seqs = 4 if platform != "tpu" else 8
    extra = _attn_overrides(attn)
    eng = InferenceEngineV2(
        model, params, dtype=jnp.float32,
        config={"max_tokens_per_batch": budget, "block_size": block_size,
                "max_context": max_context, "max_sequences": max_seqs,
                # fully-committed pool minus nothing: KV pressure on the
                # cache-on arm is absorbed by index reclaim, not eviction
                "num_blocks": max_seqs * (max_context // block_size),
                **extra})
    eng.warmup()
    rng = np.random.RandomState(0)
    system = [int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                          size=system_len)]
    sweep_end = (time.perf_counter() + sweep_budget_s
                 if sweep_budget_s else None)
    # untimed warm drive: the first serving rounds compile the sampler +
    # the chunked-prefill shapes — neither arm may pay that inside a
    # timed point (the first point's off arm would otherwise read 10x+
    # slower on pure compile time)
    _drive_prefix_arm(
        eng, None,
        {90_000_000 + i: system
         + [int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                        size=tail_len)] for i in range(2)},
        gen_len, deadline=sweep_end)
    points, skipped = [], []
    for li, n_req in enumerate(load_sweep):
        if sweep_end is not None and time.perf_counter() > sweep_end:
            skipped.append({"requests": n_req, "reason": "sweep budget"})
            continue
        tails = [[int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                              size=tail_len)]
                 for _ in range(n_req)]
        arms = {}
        try:
            for ai, arm in enumerate(("off", "on")):
                # cache-off ALWAYS runs with no cache installed (the A/B
                # must not ride a previous point's warm index)
                eng.uninstall_prefix_cache()
                uid_base = (li * 2 + ai + 1) * 1_000_000
                prompts = {uid_base + i: system + tails[i]
                           for i in range(n_req)}
                arms[arm] = _drive_prefix_arm(
                    eng, {"enabled": True} if arm == "on" else None,
                    prompts, gen_len, deadline=sweep_end)
        except _ScenarioTimeout as e:
            skipped.append({"requests": n_req, "reason": str(e)})
            skipped.extend({"requests": r, "reason": "after timeout"}
                           for r in load_sweep[li + 1:])
            break
        # byte identity by request INDEX (uids differ across arms)
        identical = all(
            arms["off"]["outs"].get((li * 2 + 1) * 1_000_000 + i)
            == arms["on"]["outs"].get((li * 2 + 2) * 1_000_000 + i)
            for i in range(n_req))
        tt_off = sorted(arms["off"]["ttft"].values())
        tt_on = sorted(arms["on"]["ttft"].values())
        mean = lambda xs: sum(xs) / max(len(xs), 1)  # noqa: E731
        ps = arms["on"]["prefix"] or {}
        point = {
            "requests": n_req,
            "byte_identical": identical,
            "ttft_mean_off_s": round(mean(tt_off), 4),
            "ttft_mean_on_s": round(mean(tt_on), 4),
            "ttft_p95_off_s": round(tt_off[int(0.95 * (len(tt_off) - 1))], 4)
            if tt_off else None,
            "ttft_p95_on_s": round(tt_on[int(0.95 * (len(tt_on) - 1))], 4)
            if tt_on else None,
            "ttft_speedup": round(mean(tt_off) / max(mean(tt_on), 1e-9), 3),
            "wall_off_s": round(arms["off"]["wall_s"], 3),
            "wall_on_s": round(arms["on"]["wall_s"], 3),
            "hit_ratio": ps.get("hit_ratio", 0.0),
            "tokens_saved": ps.get("tokens_saved", 0),
            "blocks_shared": ps.get("blocks_shared", 0),
            "cow_copies": ps.get("cow_copies", 0),
            "shed_off": arms["off"]["serve"].get("shed", 0),
            "shed_on": arms["on"]["serve"].get("shed", 0),
            # cached-arm attribution: the cached_prefix mean + prefill-stage
            # quantiles are where the TTFT speedup must show up
            "request_waterfall": _request_waterfall(
                [("on", arms["on"].pop("trace", []))]),
        }
        points.append(point)
        _emit({"metric": f"serve_prefix_point_{model_name}",
               "value": point["ttft_speedup"], "unit": "x",
               "vs_baseline": 0.0,
               "detail": {"platform": platform, "partial": True,
                          "point": point}})
    eng.uninstall_prefix_cache()
    if not points:
        raise RuntimeError(f"serve_prefix: no load point completed; "
                           f"skipped={skipped}")
    head = points[-1]  # highest completed load point
    return {
        "metric": f"serve_prefix_ttft_speedup_{model_name}",
        "value": head["ttft_speedup"],
        "unit": "x",
        "vs_baseline": head["ttft_speedup"],
        "detail": {
            "platform": platform, "model": model_name,
            "system_len": system_len, "tail_len": tail_len,
            "gen_len": gen_len, "block_size": block_size,
            "attn_impl": attn or "auto",
            "byte_identical": head["byte_identical"],
            "hit_ratio": head["hit_ratio"],
            "tokens_saved": head["tokens_saved"],
            "load_sweep": points,
            "load_points_skipped": skipped,
            "baseline": "same engine, same prompts, prefix cache off — "
                        "mean-TTFT ratio at the highest completed load "
                        "point (byte-identical outputs required)"},
    }


def run_serve_prefix():
    jax = _child_jax()

    platform = jax.devices()[0].platform
    # system prompt is deliberately SEVERAL budget chunks long: the off
    # arm's prefill takes multiple chunked forwards, the on arm's cached
    # hit skips straight to the tail — the TTFT gap is the saved chunks
    if platform == "tpu":
        ladder = [
            dict(model_name="llama-650m", load_sweep=[8, 16, 32],
                 system_len=512, tail_len=64, gen_len=16, budget=256,
                 block_size=64, max_context=1024),
            dict(model_name="llama-650m", load_sweep=[8, 16, 32],
                 system_len=512, tail_len=64, gen_len=16, budget=256,
                 block_size=64, max_context=1024, attn="xla"),
            dict(model_name="tiny", load_sweep=[8, 16, 32],
                 system_len=512, tail_len=64, gen_len=16, budget=256,
                 block_size=64, max_context=1024),
        ]
    else:
        ladder = [
            dict(model_name="tiny", load_sweep=[4, 8, 16],
                 system_len=256, tail_len=32, gen_len=4, budget=96,
                 block_size=16, max_context=384),
        ]
    rung_end = time.monotonic() + float(
        os.environ.get("DSTPU_PREFIX_SWEEP_BUDGET", 360))
    last_err = None
    for cfg in ladder:
        remaining = rung_end - time.monotonic()
        if remaining < 30:
            last_err = f"{cfg['model_name']}: skipped (rung budget)"
            break
        try:
            _emit(_serve_prefix_once(platform=platform,
                                     sweep_budget_s=remaining, **cfg))
            return
        except Exception as e:
            last_err = (f"{cfg['model_name']}[{cfg.get('attn') or 'auto'}]: "
                        f"{str(e)[:300]}")
            print(f"serve_prefix rung failed: {last_err}", file=sys.stderr)
            jax.clear_caches()
    raise RuntimeError(f"all serve_prefix rungs failed; last: {last_err}")


# ==================================================================
# rung: serve_fused (device-resident multi-step decode A-B: K fused decode
# steps per dispatch vs one host round trip per token — VERDICT r4 #1;
# reference amortization: the MII loop over ragged kernels,
# deepspeed/inference/v2/engine_v2.py:107)
# ==================================================================
def _serve_fused_once(model_name, platform, *, n_clients, prompt_len,
                      gen_len, block_size, max_context, fused_k,
                      attn=None):
    import jax
    import numpy as np

    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    cfg = get_config(model_name, max_seq_len=max_context)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size - 1,
                                            size=prompt_len)]
               for _ in range(n_clients)]

    extra = _attn_overrides(attn)

    def run(k):
        eng = InferenceEngineV2(model, params,
                                config={"max_tokens_per_batch":
                                        max(256, prompt_len),
                                        "block_size": block_size,
                                        "max_context": max_context,
                                        "max_sequences": n_clients,
                                        "num_blocks": n_clients
                                        * (max_context // block_size),
                                        "decode_steps_per_dispatch": k,
                                        **extra})
        eng.warmup()
        outs = eng.generate(prompts, max_new_tokens=gen_len)  # compile path
        eng.host_dispatches = 0
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=gen_len)
        wall = time.perf_counter() - t0
        toks = sum(len(o) for o in outs)
        return {"tok_s": round(toks / wall, 1), "wall_s": round(wall, 3),
                "tokens": toks,
                "host_dispatches_per_token":
                    round(eng.host_dispatches / max(toks, 1), 4),
                "host_ms_per_token": round(wall / max(toks, 1) * 1e3, 3)}, \
            [list(map(int, o)) for o in outs]

    per_tok, toks_a = run(1)
    fused, toks_b = run(fused_k)
    assert toks_a == toks_b, "fused decode changed greedy outputs"
    speedup = fused["tok_s"] / max(per_tok["tok_s"], 1e-9)
    return {
        "metric": f"serve_fused_decode_{model_name}",
        "value": fused["tok_s"],
        "unit": "tokens/s",
        "vs_baseline": round(speedup, 3),
        "detail": {"platform": platform, "model": model_name,
                   "clients": n_clients, "gen_len": gen_len,
                   "attn_impl": attn or "auto",
                   "decode_steps_per_dispatch": fused_k,
                   "per_token_dispatch": per_tok, "fused": fused,
                   "greedy_outputs_identical": True,
                   "baseline": "fused-vs-per-token decode throughput ratio "
                               "(host-dispatch amortization; >1 is the "
                               "win)"},
    }


def run_serve_fused():
    jax = _child_jax()

    platform = jax.devices()[0].platform
    if platform == "tpu":
        ladder = [
            dict(model_name="llama2-1b", n_clients=16, prompt_len=64,
                 gen_len=64, block_size=64, max_context=256, fused_k=16),
            dict(model_name="llama-650m", n_clients=16, prompt_len=64,
                 gen_len=64, block_size=64, max_context=256, fused_k=16),
            # XLA fallback if the Pallas serving path trips remote Mosaic
            dict(model_name="llama-650m", n_clients=16, prompt_len=64,
                 gen_len=64, block_size=64, max_context=256, fused_k=16,
                 attn="xla"),
            dict(model_name="tiny", n_clients=16, prompt_len=64,
                 gen_len=64, block_size=64, max_context=256, fused_k=16),
        ]
    else:
        ladder = [
            dict(model_name="tiny", n_clients=16, prompt_len=48,
                 gen_len=48, block_size=16, max_context=128, fused_k=16),
        ]
    last_err = None
    for cfg in ladder:
        try:
            _emit(_serve_fused_once(platform=platform, **cfg))
            return
        except Exception as e:
            last_err = (f"{cfg['model_name']}[{cfg.get('attn') or 'auto'}]: "
                        f"{str(e)[:300]}")
            print(f"serve_fused rung failed: {last_err}", file=sys.stderr)
            jax.clear_caches()
    raise RuntimeError(f"all serve_fused rungs failed; last: {last_err}")


def run_serve():
    jax = _child_jax()

    platform = jax.devices()[0].platform
    if platform == "tpu":
        ladder = [
            # the train flagship serves too: llama2-1b KV pool at 16
            # clients is ~4.3GB + 2.6GB weights on a 16GB v5e
            dict(model_name="llama2-1b", n_clients=16, reqs_per_client=2,
                 prompt_len=512, gen_len=64, budget=768, block_size=64,
                 max_context=1024),
            # 16 clients: the reference's SLA benchmark scale
            # (blogs/deepspeed-fastgen/README.md:177, Figure 5)
            dict(model_name="llama-650m", n_clients=16, reqs_per_client=2,
                 prompt_len=512, gen_len=64, budget=768, block_size=64,
                 max_context=1024),
            # XLA-attention fallback: if the Pallas serving path trips the
            # remote Mosaic compiler (opaque HTTP 500 in r5), still bank a
            # real-TPU serving number on the headline model
            dict(model_name="llama-650m", n_clients=16, reqs_per_client=2,
                 prompt_len=512, gen_len=64, budget=768, block_size=64,
                 max_context=1024, attn="xla"),
            # 8-client fallback keeps the headline MODEL comparable with
            # earlier rounds if the doubled KV pool does not fit
            dict(model_name="llama-650m", n_clients=8, reqs_per_client=2,
                 prompt_len=512, gen_len=64, budget=768, block_size=64,
                 max_context=1024),
            dict(model_name="tiny", n_clients=8, reqs_per_client=2,
                 prompt_len=512, gen_len=64, budget=768, block_size=64,
                 max_context=1024),
        ]
    else:
        ladder = [
            dict(model_name="tiny", n_clients=4, reqs_per_client=2,
                 prompt_len=48, gen_len=12, budget=64, block_size=16,
                 max_context=128),
        ]
    # ONE budget for the whole rung, carved across ladder retries — a
    # fresh per-config budget could legally outlive the parent's _spawn
    # timeout and turn back into the buffered-results kill this fixes
    rung_end = time.monotonic() + float(
        os.environ.get("DSTPU_SERVE_RUNG_BUDGET", 400))
    last_err = None
    for cfg in ladder:
        remaining = rung_end - time.monotonic()
        if remaining < 30:
            last_err = f"{cfg['model_name']}: skipped (rung budget)"
            break
        try:
            _emit(_serve_once(platform=platform,
                              scenario_budget_s=remaining / 2,  # two arms
                              **cfg))
            return
        except Exception as e:
            last_err = (f"{cfg['model_name']}[{cfg.get('attn') or 'auto'}]: "
                        f"{str(e)[:300]}")
            print(f"serve rung failed: {last_err}", file=sys.stderr)
            jax.clear_caches()
    raise RuntimeError(f"all serve rungs failed; last: {last_err}")


# ======================================================================
# parent orchestration
# ======================================================================
def _parse_lines(text):
    results = []
    for line in (text or "").strip().splitlines():
        try:
            parsed = json.loads(line)
            if isinstance(parsed, dict) and "metric" in parsed:
                results.append(parsed)
        except json.JSONDecodeError:
            continue
    return results


def _spawn(rung, timeout, env_overrides):
    """Run one rung child. Returns (results, err) — BOTH can be non-empty: a
    child that banked some JSON lines and then died/hung keeps its partial
    results AND reports the failure."""
    env = dict(os.environ)
    env[RUNG_ENV] = rung
    env.update(env_overrides)
    # Popen + communicate instead of subprocess.run: run() handles ONLY
    # TimeoutExpired with output capture — any other exception (the
    # SIGTERM handler's _Killed, notably) kills the child and closes the
    # pipes without draining them, losing every partial line the child
    # already flushed. The kill path below drains first and hangs the
    # salvaged results on the exception for main()'s aggregate flush.
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env)
    try:
        out, err_txt = proc.communicate(timeout=timeout)
    except BaseException as exc:
        proc.kill()
        try:
            out, _ = proc.communicate(timeout=10)
        except _Killed as killed:
            # SIGTERM landed during the drain itself (e.g. while handling
            # a rung timeout): the kill outranks whatever got us here — it
            # must reach main()'s aggregate flush, not be swallowed. The
            # child is already SIGKILLed, so one bounded retry recovers the
            # pipe content (communicate() keeps partial output across an
            # interrupted call and allows retrying).
            try:
                out, _ = proc.communicate(timeout=2)
            except BaseException:
                out = ""
            killed.results = _parse_lines(out)
            killed.rung = rung
            raise
        except BaseException:
            out = ""
        results = _parse_lines(out)
        if isinstance(exc, subprocess.TimeoutExpired):
            return results, f"{rung}: timeout after {timeout}s"
        if isinstance(exc, _Killed):
            exc.results = results
            exc.rung = rung
        raise
    results = _parse_lines(out)

    def diag():
        """Prefer the exception over trailing log spam: the last
        'rung failed:'/Traceback block of stderr, else raw tails."""
        txt = err_txt or ""
        for marker in ("rung failed:", "Traceback (most recent call last)"):
            i = txt.rfind(marker)
            if i >= 0:
                return txt[i:i + 1200]
        return (txt[-1000:] + (out or "")[-300:])

    if proc.returncode != 0:
        return results, f"{rung}: rc={proc.returncode}: {diag()}"
    if not results:
        return results, f"{rung}: no metric emitted: {diag()}"
    return results, None


CPU_ENV = {"JAX_PLATFORMS": "cpu", "DSTPU_ACCELERATOR": "cpu"}


# multichip is the CPU virtual-device sim by construction — it runs under
# CPU_ENV by name (it measures the SPMD sim, not the silicon, says so in
# its lines, and is priced accordingly at the tail of the plan)
# train_ring is likewise CPU-sim by construction: it needs a 2-virtual-
# device seq mesh (forced host platform device count), and its flash arm
# runs the Pallas kernels in interpret mode off-TPU — an A/B of dispatch
# structure under the MFU ledger, not of kernel speed
RING_ENV = {**CPU_ENV,
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
# (rung, timeout seconds, child env). There is no CPU plan.
TPU_PLAN = [("kernels_micro", 400, {}),
            ("kernels", 600, {}),
            ("train", 1200, {}),
            ("serve", 700, {}),
            ("serve_fused", 500, {}),
            ("serve_prefix", 400, {}),
            ("serve_goodput", 700, {}),
            ("multichip", 400, CPU_ENV),
            ("offload", 500, CPU_ENV),
            ("fleet", 500, CPU_ENV),
            ("train_ring", 500, RING_ENV)]


class _Killed(Exception):
    """Raised from the SIGTERM handler: the outer harness' `timeout` sends
    SIGTERM before SIGKILL (rc=124). Raising is the only way to interrupt a
    blocking subprocess.run wait, and the whole point is to reach the
    aggregate-flush path below with whatever results exist — the r05
    failure was dying with every rung line buffered in children."""


def _bench_diff_gate(all_results):
    """Round-over-round regression gate: diff this round's in-memory
    metric lines against the newest checked-in ``BENCH_r*.json`` with
    ``tools/bench_diff.py`` and print ONE ``BENCH_DIFF`` verdict line
    (partial per-scenario lines are exempt inside diff_rounds). Advisory
    by contract — it does not move the exit code; the verdict line and the
    ``bench_diff`` block on the aggregate are what a round script gates
    on. Returns the summary dict, or None when no baseline exists."""
    import glob

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sys.path.insert(0, os.path.join(here, "tools"))
        import bench_diff as bd
    except Exception as e:  # the gate must never take the bench down
        print(f"BENCH_DIFF skipped: tools/bench_diff.py unusable ({e})",
              file=sys.stderr)
        return None
    finally:
        sys.path.pop(0)
    rounds = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not rounds:
        print("BENCH_DIFF skipped: no prior BENCH_r*.json baseline")
        return None
    prev = rounds[-1]
    old = bd.load_round(prev)
    new = {}
    for r in all_results:
        bd._ingest(r, new)
    if not old or not new:
        print(f"BENCH_DIFF skipped: empty "
              f"{'baseline' if not old else 'round'}")
        return None
    threshold = float(os.environ.get("DSTPU_BENCH_DIFF_THRESHOLD", "0.10"))
    try:
        diff = bd.diff_rounds(old, new, threshold)
    except Exception as e:
        print(f"BENCH_DIFF skipped: diff failed ({e})", file=sys.stderr)
        return None
    regs = diff["regressions"]
    verdict = "REGRESSED" if regs else "OK"
    print(f"BENCH_DIFF {verdict} vs {os.path.basename(prev)} "
          f"(threshold {threshold:.0%}): "
          + (", ".join(regs) if regs else "no regressions beyond threshold"))
    return {"baseline": os.path.basename(prev), "threshold": threshold,
            "verdict": verdict, "regressions": regs,
            "metrics_compared": sum(1 for r in diff["rows"]
                                    if r.get("status") not in
                                    ("added", "removed"))}


def main():
    """Probe, then run the TPU plan one rung child at a time. Returns the
    exit code: 2 when no TPU answers (nothing runs), 1 when a rung failed,
    hung or was cut, 0 otherwise."""
    import signal

    def _on_term(signum, frame):
        raise _Killed(signum)

    signal.signal(signal.SIGTERM, _on_term)
    deadline = time.monotonic() + float(
        os.environ.get("DSTPU_BENCH_DEADLINE", 3300))
    all_results, errors = [], []
    # the try must start HERE, not at the rung loop: the probe below is
    # exactly where an outer `timeout -s TERM ... 45` lands its SIGTERM,
    # and a _Killed escaping uncaught skips the aggregate flush this
    # handler exists to guarantee
    try:
        # ONE probing child, gone before any rung child needs the chip
        res, err = _spawn("probe", 120, {})
        platform = res[0]["detail"].get("platform") if res else None
        if platform != "tpu":
            print(f"bench: no TPU answered (probe: "
                  f"{platform or (err or 'no output')[-700:]}); there is no "
                  f"CPU plan", file=sys.stderr)
            return 2
        for rung, timeout, env in TPU_PLAN:
            remaining = deadline - time.monotonic()
            if remaining < 60:
                errors.append(f"{rung}: skipped (deadline)")
                continue
            results, err = _spawn(rung, min(timeout, remaining), env)
            for r in results:
                _emit(r)
            all_results.extend(results)
            if err:
                errors.append(err)
    except _Killed as e:
        # a second SIGTERM during the salvage emits below must not
        # interrupt them — ignore it before doing any more work
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        # a kill that landed mid-rung carries whatever the child had
        # flushed (salvaged by _spawn's drain) — bank it like any rung
        salvaged = getattr(e, "results", [])
        for r in salvaged:
            _emit(r)
        all_results.extend(salvaged)
        rung = getattr(e, "rung", None)
        if rung:
            errors.append(f"{rung}: killed mid-rung (SIGTERM)")
        errors.append(f"bench: SIGTERM ({e.args[0]}) — flushing "
                      f"partial aggregate (outer timeout imminent)")
    # the tail below IS the flush: a second SIGTERM must not interrupt it
    # (the outer timeout's SIGKILL is the backstop)
    signal.signal(signal.SIGTERM, signal.SIG_IGN)

    # final aggregated headline: the train number if we have one, else
    # serve, else the best kernel line — with every rung under detail.rungs
    def best_train(lines):
        """The train rung A-Bs perf levers (attn impl, remat) — the best
        variant is the round's number. MFU ratios only compare within a
        platform, so prefer the TPU subset when it exists."""
        tpu = [r for r in lines
               if r.get("detail", {}).get("platform") == "tpu"]
        pool = tpu or lines
        return max(pool, key=lambda r: r.get("vs_baseline") or 0.0)

    def _is_partial(r):
        return bool((r.get("detail") or {}).get("partial"))

    def pick(prefix):
        # partial per-scenario flush lines (serve arms, goodput load
        # points) are evidence, not headlines — prefer a complete rung
        # line, fall back to a partial only when nothing else survived
        full = [r for r in all_results
                if r["metric"].startswith(prefix) and not _is_partial(r)]
        cands = full or [r for r in all_results
                         if r["metric"].startswith(prefix)]
        if not cands:
            return None
        if prefix == "train":
            return best_train(cands)
        return cands[0]

    head = pick("train") or pick("serve") or pick("kernel")
    if head is None:
        for e in errors:
            print(f"bench: {e[-700:]}", file=sys.stderr)
        return 1
    # prefer a REAL-TPU line as the headline over a CPU line of an
    # earlier-preferred rung (CPU train numbers are not the perf story)
    tpu_lines = [r for r in all_results
                 if r.get("detail", {}).get("platform") == "tpu"
                 and not _is_partial(r)]
    if head.get("detail", {}).get("platform") != "tpu" and tpu_lines:
        for prefix in ("train", "serve", "kernel"):
            cands = [r for r in tpu_lines
                     if r["metric"].startswith(prefix)]
            if cands:
                # same best-variant rule as pick() — not emission order
                head = best_train(cands) if prefix == "train" else cands[0]
                break
    rest = [r for r in all_results if r is not head]
    head = dict(head)
    head["detail"] = dict(head.get("detail", {}))
    head["detail"]["rungs"] = rest
    if errors:
        head["detail"]["rung_errors"] = [e[-700:] for e in errors]
    # round-over-round regression verdict vs the newest BENCH_r*.json
    # (tools/bench_diff.py; partial lines exempt) — printed AND attached
    try:
        bd_summary = _bench_diff_gate(all_results + [head])
    except Exception as e:
        bd_summary = None
        print(f"BENCH_DIFF skipped: {e}", file=sys.stderr)
    if bd_summary is not None:
        head["detail"]["bench_diff"] = bd_summary
    _emit(head)
    return 1 if errors else 0


if __name__ == "__main__":
    rung = os.environ.get(RUNG_ENV)
    if rung == "probe":
        run_probe()
    elif rung == "kernels_micro":
        run_kernels_micro()
    elif rung == "kernels":
        run_kernels()
    elif rung == "train":
        run_train()
    elif rung == "train_ring":
        run_train_ring()
    elif rung == "serve":
        run_serve()
    elif rung == "serve_fused":
        run_serve_fused()
    elif rung == "serve_prefix":
        run_serve_prefix()
    elif rung == "serve_goodput":
        run_serve_goodput()
    elif rung == "fleet":
        run_fleet()
    elif rung == "multichip":
        run_multichip()
    elif rung == "offload":
        run_offload()
    else:
        sys.exit(main())
