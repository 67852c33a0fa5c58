"""On-silicon kernel triage + block-size autotune (each section prints one
JSON line, so a cut run still banks evidence).

Sections, cheapest first:
  calib   — XLA matmul at known-FLOP shapes: separates dispatch overhead
            from device compute (a 1.1 TFLOP matmul at v5e peak is ~6 ms;
            if measured time is tens of ms, the gap is dispatch).
  flash   — the three flash-attention kernels alone at the training cells'
            shape (the tree's module beside a parent checkout's), with a
            block_q/block_k sweep, the bf16-operand probe and the parity
            against XLA:  flash [--parent DIR] [--seq N ...] [--sweep]
            [--probe] [--parity]
  paged   — the paged-attention kernel alone at the serving cells' tiles
            (heads x whole blocks of context x a one-row tile or a prompt's
            atom), the tree's module beside a parent checkout's: us a tile,
            the fit ``a + b x blocks``, the share of 819 GB/s; the tree with
            every other tile dead; the outputs against the parent's bit for
            bit:  paged [--parent DIR ...] [--kind K ...] [--tile row atom]
            [--gaps] [--parity]
  retention — the power-retention state step alone at ``brumby-rollout-sat``'s
            shape: the tree's one-pass walk and its copies with no read-out
            beside a parent checkout's kernel (PR 49's at its two blocks), us
            a (row, head) and the share of the HBM peak; the parity against
            XLA over several steps:  retention [--parent DIR ...] [--tiles]
            [--copies] [--parity]

  kda     — the gated delta rule alone at ``solar2-agent-sat``'s shape (256
            rows on 256 slots + the sink, 3 layers, 64 heads of a [128, 128]
            float32 state): the state step's kernel at several heads a grid
            step, ms a layer and the share of the HBM peak; the chunked
            form's pieces as the XLA loop and as the kernel ``kda_piece`` at
            several heads a grid step (ms a piece, the bytes it has to move,
            their share of the HBM peak), eight pieces of eight slots and a
            12-piece chunk of ONE slot; with ``--parity`` both entries, the
            chunked one in both forms, against the SEQUENTIAL float32
            recurrence on the chip, a decay strong enough to overflow a
            naive ``e^-G`` and a slot's state handed from piece to piece
            among them:  kda [--heads N ...] [--piece-heads N ...] [--parity]

  conv    — the one-token rows' convolution ALONE at the two cells that
            run it (``solar2-agent-sat``: 256 rows x 24,576 channels, no
            bias; ``nemo3-reason-sat``: 128 x 6,144, bias; kernel 4, a
            bfloat16 pool): the tail's kernel at several (slots a grid step,
            channels a tile) beside the XLA form (the tree's, and ``--parent
            DIR``'s ``conv_step`` as it stands), ms a layer and the tail's
            GB/s against 819; ``--parity`` holds the kernel against the XLA
            form on the chip over several steps. ``--pieces``: the PIECES'
            convolution instead (768 rows in pieces of 64 | 512 in pieces of
            128: a mixed forward's), the XLA loop beside the kernel
            ``conv_pieces`` at each of ``--channels`` channels a grid step x
            ``--strip`` lanes a strip, us a piece and a forward's worth
            beside the bytes' floor, over eight pieces of eight slots, a
            chunk of ONE slot and a ragged round; with ``--parity`` both
            forms against a plain convolution of each whole sequence:
            conv [--parent DIR] [--slots N ...] [--lanes N ...] [--parity]
            conv --pieces [--channels N ...] [--strip N ...] [--parity]

  combine — the experts' combine ALONE at the served sparse cells'
            (experts a token, model width, experts held: read off
            ``BENCHMARK.json``'s configurations) and 32 / 128 / 768 tokens a
            forward: the scatter-add of PRs 26-57
            beside ``parallel/moe.combine_rows`` (one gather of a token's k
            rows out of the tile layout, a weighted sum in float32), us a
            layer and GB/s, and the candidates for the inverse of the sort,
            each alone:
            combine [--parent DIR] [--cell C ...] [--rows N ...]

  bsa     — block-sparse attention chosen from pooled keys and lightning
            attention ALONE at ``sala-docs-sat``'s shape (32 heads over 2 KV
            heads of 128, pages of 64, 96 pages a row and KV head; 32
            lightning heads of a [128, 128] float32 state), at each of
            ``--ctx`` tokens of context (32 k, 64 k, 96 k): the pooled keys'
            write, the block scores and the selection of one 128-row atom and
            of 8 one-token rows, the atom under its selection of BLOCKS a KV
            head through the ragged kernel (``kernel`` the masked kernel
            alone, ``xla`` whatever the program runs around it) and, with
            ``--parent DIR``, that checkout's kernel under the same selection
            widened to KEYS as PRs 59-67 handed it (the widening inside the
            program, so ``xla`` holds it), the one-token rows over their
            own page tables beside a dense row over its whole context, ms a
            call; the lightning state step (8 rows: ms a layer and the share
            of the HBM peak) and six 128-row pieces; ``--parity`` holds the
            selection's kernel and both attention routes against their
            ``jax.numpy`` forms (and the atom against the parent's) on the
            chip first:
            bsa [--ctx N ...] [--parent DIR] [--parity]

  proj    — a projection ALONE, ``[T, in] x W`` at 16 / 32 / 48 / 256 /
            768 rows and every served cell's ``(in, out)`` (read off
            ``BENCHMARK.json``'s configurations: q and k/v, a lightning
            layer's, latent attention's ``w_qb``, an indexer's ``w_qi``), W
            stored ``[in, out]`` (the model's public tree) and ``[out, in]``
            (``model.serving_layout``), and latent attention's two batched
            products a head over ``w_kvb`` (whole and cut inside, or its
            part alone, head-major), sliced from
            a stack inside a scan as the forwards slice it, ``--passes`` of
            the stack (a looped model's second loop is what makes the v5e
            compiler copy a whole ``[in, out]`` stack): us a layer, the GB/s
            of the weights and the MiB of temporaries of each program, and
            the largest copy in its text:
            proj [--cell C ...] [--rows N ...] [--layers N] [--passes N]

Usage:  python tools/tpu_tune.py
            [calib|flash|paged|retention|dsa|kda|conv|combine|bsa|proj|all]
"""
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# the flash and paged sections import the package from the checkout
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

V5E_PEAK = 197e12
V5E_HBM = 819e9


def _bench_chain(fn_one, x0, extra_args, iters):
    """Per-iteration device time of ``fn_one(x, *extra) -> x'`` measured as
    ``iters`` data-dependent applications inside ONE jitted fori_loop — a
    single dispatch, so per-call dispatch latency (enough to swamp a sub-ms
    kernel) cancels out. The chained data dependency defeats CSE/DCE. The
    one-dispatch floor is measured separately and subtracted. Returns
    ``(seconds, "chained" | "dispatch_bound")``."""
    def chained(x, extra):
        return jax.lax.fori_loop(0, iters,
                                 lambda i, xx: fn_one(xx, *extra), x)

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


def bench(fn, args, iters=10):
    """Wall-time per call including dispatch (used where per-dispatch cost
    IS the quantity of interest, e.g. the calib section)."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def emit(section, **kw):
    print(json.dumps({"section": section, **kw}), flush=True)


def calib():
    key = jax.random.PRNGKey(0)
    rows = []
    for n in (2048, 4096, 8192):
        a = jax.random.normal(key, (n, n), jnp.bfloat16)
        dt_disp = bench(jax.jit(lambda a, b: a @ b), (a, a))
        dt_dev, how = _bench_chain(lambda x, b: (x @ b).astype(x.dtype),
                                   a, (a,), 10)
        fl = 2 * n ** 3
        rows.append({"matmul": n,
                     "wall_ms_per_call": round(dt_disp * 1e3, 3),
                     "device_ms": round(dt_dev * 1e3, 3),
                     "dispatch_ms": round((dt_disp - dt_dev) * 1e3, 3),
                     "timing": how,
                     "tflops": round(fl / dt_dev / 1e12, 1),
                     "peak_frac": round(fl / dt_dev / V5E_PEAK, 3)})
    # dispatch floor: a trivial add, timed the same way
    x = jnp.ones((8, 128), jnp.bfloat16)
    dt0 = bench(jax.jit(lambda x: x + 1), (x,), iters=20)
    emit("calib", platform=jax.devices()[0].platform,
         dispatch_floor_ms=round(dt0 * 1e3, 3), matmuls=rows)


# the training cells' attention (mistral-7b widths, BENCHMARK.json's two
# training cells): 8,192 tokens a chip a step, 32 query heads over 8, bf16
FLASH_CELL = dict(tokens=8192, heads=32, kv_heads=8, head_dim=128,
                  window=4096)
FLASH_BLOCKS = (256, 512, 1024, 2048)
# blocks a grid step copies (walked in the rule's compute tiles)
FLASH_STEPS = ((512, 512), (1024, 1024), (1024, 2048), (2048, 1024),
               (2048, 2048), (4096, 4096), (8192, 8192))


def _load_op(root, op, name):
    """``ops/<op>.py`` of the checkout at ``root`` as a module ``name`` of
    its own, so the parent's kernels run beside the tree's in one process."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, os.path.join(
        root, "deepspeedsyclsupport_tpu", "ops", op + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_flash(root):
    """``ops/flash_attention.py`` (it imports nothing of the package)."""
    return _load_op(root, "flash_attention", "flash_" + (
        os.path.basename(os.path.abspath(root)) or "tree"))


def _flash_operands(seq, seed=0):
    c = FLASH_CELL
    b = c["tokens"] // seq
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (b, seq, h, c["head_dim"])
    q = jax.random.normal(ks[0], shape(c["heads"]), jnp.bfloat16)
    k = jax.random.normal(ks[1], shape(c["kv_heads"]), jnp.bfloat16)
    v = jax.random.normal(ks[2], shape(c["kv_heads"]), jnp.bfloat16)
    w = jax.random.normal(ks[3], shape(c["heads"]), jnp.bfloat16)
    return q, k, v, w


def _flash_step(mod, name, **blocks):
    """Forward and all three gradients in one program named ``name``: the
    three custom calls of a training step's layer."""
    def step(q, k, v, w):
        def loss(q, k, v):
            o = mod.flash_attention(q, k, v, causal=True,
                                    window=FLASH_CELL["window"], **blocks)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    step.__name__ = name
    return jax.jit(step)


def _traced_kernels(steps, args, runs=3, kernel_of=None, carry=None):
    """Run every ``{name: compiled step}`` ``runs`` times under ONE profiler
    trace and return ``{name: {"fwd" | "dq" | "dkv": median ms, "xla": ms of
    everything else in the program}}``, the kernels told apart as the
    benchmark's ``flash_roofline`` tells them (or by ``kernel_of``, custom
    call's text -> name). ``carry``: a donated first argument each step
    takes and returns first (a pool held once)."""
    import glob
    import statistics
    import tempfile

    from benchmark import trace
    if kernel_of is None:
        from benchmark.metrics.flash_roofline import kernel_of

    def run(f):
        nonlocal carry
        if carry is None:
            return jax.block_until_ready(f(*args))
        carry, out = f(carry, *args)
        return jax.block_until_ready(out)

    for f in steps.values():
        run(f)                                              # warm
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        for f in steps.values():
            for _ in range(runs):
                run(f)
        jax.profiler.stop_trace()
        tr = trace.read_xplane(sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb")))[-1])
    plane = sorted(tr["devices"])[0]
    seen = {}
    for program, text, _start, dur in trace.ops_by_program(tr, plane):
        kind = (kernel_of(text) if trace.op_kind(text) == "kernel"
                else "xla")
        seen.setdefault(program, {}).setdefault(kind, []).append(dur)
    out = {}
    for name in steps:
        row = seen.get(name, {})
        out[name] = {k: round(1e3 * statistics.median(v), 4)
                     for k, v in row.items() if k != "xla"}
        out[name]["calls"] = {k: len(v) for k, v in row.items()
                              if k != "xla"}
        out[name]["xla"] = round(1e3 * sum(row.get("xla", [])) / runs, 4)
    return out


def _flash_roofline(kernel, ms, seq):
    """Share of the bf16 peak by ``benchmark/flops.py``'s product counts."""
    from benchmark import flops

    c = FLASH_CELL
    need = flops.flash_flops(kernel, c["tokens"] // seq, c["heads"], seq,
                             c["head_dim"], c["window"])
    return round(100 * need / V5E_PEAK / (ms * 1e-3), 1)


def _flash_probe():
    """Does Mosaic's product at the default precision see a float32 copy of a
    bf16 operand as the bf16 operand (PR 46's finding, at the flash tile)?
    Worst absolute difference between the two products; 0.0 is bit for bit."""
    from jax.experimental import pallas as pl

    def kern(a_ref, b_ref, p_ref, qk32, qk16, pv32, pv16):
        a, b, p = a_ref[...], b_ref[...], p_ref[...]
        nt = (((1,), (1,)), ((), ()))
        f32 = jnp.float32
        qk32[...] = jax.lax.dot_general(a.astype(f32), b.astype(f32), nt,
                                        preferred_element_type=f32)
        qk16[...] = jax.lax.dot_general(a, b, nt, preferred_element_type=f32)
        pv32[...] = jnp.dot(p, b.astype(f32), preferred_element_type=f32)
        pv16[...] = jnp.dot(p.astype(b.dtype), b, preferred_element_type=f32)

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    a = jax.random.normal(ks[0], (512, 128), jnp.bfloat16)
    b = jax.random.normal(ks[1], (512, 128), jnp.bfloat16)
    p = jax.random.uniform(ks[2], (512, 512), jnp.float32)
    f32 = jnp.float32
    qk32, qk16, pv32, pv16 = pl.pallas_call(
        kern, out_shape=[jax.ShapeDtypeStruct((512, 512), f32)] * 2
        + [jax.ShapeDtypeStruct((512, 128), f32)] * 2)(a, b, p)
    return {"qk_f32_copy_vs_bf16": float(jnp.max(jnp.abs(qk32 - qk16))),
            "pv_f32_p_vs_bf16_p": float(jnp.max(jnp.abs(pv32 - pv16)))}


def _flash_parity(mods, seq=2048, batch=2):
    """Forward and dq / dk / dv of each module against the XLA reference in
    float32 at the cells' widths: worst absolute error over worst reference
    magnitude, per output."""
    from deepspeedsyclsupport_tpu.models.layers import reference_attention

    q, k, v, w = (x[:batch] for x in _flash_operands(seq, seed=7))
    f32 = jnp.float32

    def both(attn):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(f32) * w.astype(f32)), o
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return (o,) + g

    ref = jax.jit(lambda: both(lambda q, k, v: reference_attention(
        q.astype(f32), k.astype(f32), v.astype(f32), causal=True,
        window=FLASH_CELL["window"])))()
    out = {}
    for tag, mod in mods.items():
        got = jax.jit(lambda mod=mod: both(lambda q, k, v: mod.flash_attention(
            q, k, v, causal=True, window=FLASH_CELL["window"])))()
        out[tag] = {n: float(jnp.max(jnp.abs(g.astype(f32) - r.astype(f32)))
                             / jnp.max(jnp.abs(r.astype(f32))))
                    for n, g, r in zip(("fwd", "dq", "dk", "dv"), got, ref)}
    return out


def flash(argv=()):
    """The three flash kernels ALONE at the training cells' shape: each
    custom call's device time read off a profiler trace (as the benchmark's
    ``flash_roofline`` reads it), the tree's module beside the parent's
    (``--parent DIR``, a checkout of the parent commit), at the default
    blocks, over a ``(block_q, block_k)`` sweep (``--sweep``: copied and
    computed as one tile) and over the block a grid step COPIES under the
    rule's compute tile, or another (``--steps [--tile N ...]``: the tree's
    ``_PREFERRED_BLOCK`` and ``_COMPUTE_TILE``), at
    ``--seq`` tokens a sequence (8,192 tokens a step whatever the length);
    ``--probe`` and ``--parity`` make the bf16-operand and the
    against-XLA checks."""
    import argparse

    ap = argparse.ArgumentParser(prog="tpu_tune.py flash")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--seq", type=int, nargs="*", default=[2048])
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--steps", action="store_true")
    ap.add_argument("--tile", type=int, nargs="*", default=[])
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--parity", action="store_true")
    a = ap.parse_args(list(argv))
    mods = {"tree": _load_flash(os.path.join(os.path.dirname(__file__),
                                             ".."))}
    if a.parent:
        mods["parent"] = _load_flash(a.parent)
    if a.probe:
        emit("flash_probe", **_flash_probe())
    if a.parity:
        emit("flash_parity", **_flash_parity(mods))
    for seq in a.seq:
        grid = [(bq, bk) for bq in FLASH_BLOCKS for bk in FLASH_BLOCKS
                if bq <= seq and bk <= seq]
        # (row name, module, flash_attention's keywords, the rule's blocks)
        plan = [(f"{tag}_{seq}_default", mod, {}, None)
                for tag, mod in mods.items()]
        if a.sweep:
            plan += [(f"{tag}_{seq}_{bq}x{bk}", mod,
                      dict(block_q=bq, block_k=bk), None)
                     for tag, mod in mods.items() for bq, bk in grid]
        tree = mods["tree"]
        rule, tile = tree._PREFERRED_BLOCK, tree._COMPUTE_TILE
        if a.steps:
            plan += [(f"tree_{seq}_tile{c}_steps{bq}x{bk}", tree, {},
                      (c, (bq, bk))) for c in [tile] + a.tile
                     for bq, bk in FLASH_STEPS
                     if c <= min(bq, bk) and max(bq, bk) <= seq]
        args = _flash_operands(seq)
        steps, failed = {}, {}
        for name, mod, kw, patch in plan:
            if patch is not None:
                tree._COMPUTE_TILE = patch[0]
                tree._PREFERRED_BLOCK = patch[1]
            try:
                steps[name] = _flash_step(mod, name, **kw).lower(
                    *args).compile()
            except Exception as e:                 # e.g. over the VMEM limit
                failed[name] = str(e).splitlines()[0][:160]
            finally:
                tree._PREFERRED_BLOCK, tree._COMPUTE_TILE = rule, tile
        rows = _traced_kernels(steps, args)
        for name, row in rows.items():
            row["roofline_pct"] = {k: _flash_roofline(k, row[k], seq)
                                   for k in ("fwd", "dq", "dkv") if k in row}
            row["three_ms"] = round(sum(row.get(k, 0)
                                        for k in ("fwd", "dq", "dkv")), 4)
        emit("flash", seq=seq, cell=FLASH_CELL, rows=rows, failed=failed)


# The serving cells' attention tiles (query heads, kv heads, head_dim; a
# latent pool: one row of ``d`` lanes a token, the value its leading ``v``;
# ``sel``: under an indexer's selection), blocks of 64 keys, bf16. ``atom``:
# rows of a prompt's tile (``default_atom_rows`` of the cell's engine).
PAGED_BLOCK = 64
PAGED_KINDS = {
    "32/32": dict(h=32, kvh=32, d=128, atom=128),     # phi-2 (80 stored as 128)
    "16/16": dict(h=16, kvh=16, d=128, atom=128),     # OLMoE, Ouro
    "32/2": dict(h=32, kvh=2, d=128, atom=128),       # Nemotron
    "32/4sel": dict(h=32, kvh=4, d=128, atom=128, sel=True),        # Keye
    "128/8": dict(h=128, kvh=8, d=128, atom=64),      # command-a-plus
    "32/latent": dict(h=32, d=640, v=512, atom=128),  # Xing4: two head tiles
    "128/latent": dict(h=128, d=640, v=512, atom=16),  # DeepSeek-V2 (576 as 640)
}
PAGED_CONTEXTS = (1, 2, 4, 8, 32)     # whole blocks of context a tile
PAGED_LONG = (128,)                   # and, a latent or selected pool's tile
PAGED_TILES = {"row": 32, "atom": 8}  # tiles a call, by the tile's height
PAGED_CALLS = 8                       # calls a program (a forward's layers)


def _load_paged(root):
    """``ops/paged_attention.py`` (it imports nothing of the package)."""
    tag = os.path.basename(os.path.abspath(root)).strip("_")
    return tag, _load_op(root, "paged_attention", "paged_" + tag)


def _paged_rows(kind, height):
    """Rows of a tile of that height."""
    return 1 if height == "row" else kind["atom"]


def _paged_call(mod, kind, height, q, pools, tables, pos0, qlen, layer, sel,
                window=None):
    """One call of ``mod``'s kernel under the name a forward gives it."""
    return mod.ragged_prefill_attention_pallas(
        q, pools[0], pools[1] if len(pools) > 1 else None, tables, pos0,
        qlen, block_size=PAGED_BLOCK, layer=layer, window=window,
        v_dim=kind.get("v"), sel=sel,
        name="paged_decode" if height == "row" else "ragged_prefill")


def _paged_lane_bytes(kind):
    """Bytes of one cached token as the copies move them: K and V (a latent
    pool: its one row) in bf16, the row's lanes tiled up to whole 128s."""
    lanes = -(-kind["d"] // 128) * 128
    return lanes * 2 * (1 if "v" in kind else 2 * kind["kvh"])


def _paged_operands(kind, height, bps, seed=0):
    """``(q, pools, tables)`` of one call: a pool of two layers that holds
    every tile's ``bps`` blocks once, dealt to the tables in shuffled
    order."""
    tiles = PAGED_TILES[height]
    rows = _paged_rows(kind, height)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    slots = tiles * bps * PAGED_BLOCK
    row = (kind["d"],) if "v" in kind else (kind["kvh"], kind["d"])
    make = jax.jit(lambda key: jax.random.normal(key, (2, slots) + row,
                                                 jnp.bfloat16))
    pools = (make(ks[1]),) if "v" in kind else (make(ks[1]), make(ks[2]))
    q = jax.random.normal(ks[0], (tiles, rows, kind["h"], kind["d"]),
                          jnp.bfloat16)
    tables = np.asarray(jax.random.permutation(ks[3], tiles * bps),
                        np.int32).reshape(tiles, bps)
    return q, pools, jnp.asarray(tables)


def _paged_tiles(kind, height, blocks, gaps=False):
    """``(pos0, qlen)`` of a call's tiles at ``blocks`` whole blocks of
    context each; ``gaps``: every other tile dead (each live tile then
    starts cold)."""
    tiles = PAGED_TILES[height]
    keys = np.full(tiles, blocks * PAGED_BLOCK)
    qlen = np.minimum(_paged_rows(kind, height), keys)
    if gaps:
        qlen[1::2] = 0
    pos0 = np.where(qlen > 0, keys - qlen, 0)
    return jnp.asarray(pos0, jnp.int32), jnp.asarray(qlen, jnp.int32)


def _paged_selection(kind, height, bps, seed=1):
    """An indexer's selection ``[tiles, rows, keys]``: 3 keys in 10."""
    if not kind.get("sel"):
        return None
    return (jax.random.uniform(
        jax.random.PRNGKey(seed),
        (PAGED_TILES[height], _paged_rows(kind, height),
         bps * PAGED_BLOCK)) < 0.3).astype(jnp.int8)


def _paged_step(mod, name, kind, height, sel, calls=PAGED_CALLS):
    """``calls`` calls of the kernel in one program named ``name``, the
    pool's layers by turns (as a forward's layer loop calls it)."""
    one = functools.partial(_paged_call, mod, kind, height, sel=sel)

    def step(q, pools, tables, pos0, qlen):
        out = one(q, pools, tables, pos0, qlen, jnp.int32(0))
        return jax.lax.fori_loop(
            1, calls, lambda i, acc: acc + one(
                q, pools, tables, pos0, qlen, jax.lax.rem(i, 2)), out)
    step.__name__ = name
    return jax.jit(step)


def _paged_parity(mods, kinds, heights):
    """The tree's kernel against the parent's ON THE CHIP, output for
    output: contexts of 1 to 8 blocks that end inside a block, a dead tile
    between live ones and two at the end, with and without a sliding
    window. ``differ``: outputs that are not the parent's bit for bit."""
    (tag, parent), = [(t, m) for t, m in mods.items() if t != "tree"]
    for kname in kinds:
        kind = PAGED_KINDS[kname]
        for height in heights:
            if kind.get("sel") and height == "row":
                continue
            bps = 8
            q, pools, tables = _paged_operands(kind, height, bps, seed=5)
            tiles = PAGED_TILES[height]
            rows = _paged_rows(kind, height)
            rng = np.random.default_rng(tiles)
            keys = rng.integers(1, bps * PAGED_BLOCK, tiles)
            qlen = np.minimum(rng.integers(1, rows + 1, tiles), keys)
            qlen[[2, tiles - 2, tiles - 1]] = 0
            pos0 = np.where(qlen > 0, keys - qlen, 0)
            sel = _paged_selection(kind, height, bps)
            for window in (None, 3 * PAGED_BLOCK):
                got = [np.asarray(jax.jit(
                    lambda q, pools, mod=mod: _paged_call(
                        mod, kind, height, q, pools, tables,
                        jnp.asarray(pos0, jnp.int32),
                        jnp.asarray(qlen, jnp.int32), jnp.int32(1), sel,
                        window))(q, pools).astype(jnp.float32))
                       for mod in (mods["tree"], parent)]
                emit("paged_parity", kind=kname, tile=height, window=window,
                     against=tag, outputs=int(got[0].size),
                     differ=int(np.count_nonzero(got[0] != got[1])),
                     worst=float(np.max(np.abs(got[0] - got[1]))),
                     finite=bool(np.isfinite(got[0]).all()))


def paged(argv=()):
    """The paged-attention kernel ALONE at the serving cells' tiles, its
    device time read off a profiler trace: us a tile at contexts of 1 to 32
    whole blocks (a latent or selected pool's also 128) for a one-row tile
    (32 a call) and a prompt's atom (8 a call), the fit ``a + b x blocks``
    over them, and the share of 819 GB/s the blocks' bytes make of the
    time; the tree's module beside each ``--parent DIR``'s. ``--gaps`` adds
    the tree with every other tile dead (each live tile then starts cold:
    what the hand-over between tiles is worth, in the tree's own code),
    ``--parity`` holds the tree's outputs against the parent's bit for
    bit."""
    import argparse

    ap = argparse.ArgumentParser(prog="tpu_tune.py paged")
    ap.add_argument("--parent", action="append", default=[])
    ap.add_argument("--kind", nargs="*", default=list(PAGED_KINDS))
    ap.add_argument("--tile", nargs="*", default=list(PAGED_TILES))
    ap.add_argument("--gaps", action="store_true")
    ap.add_argument("--parity", action="store_true")
    a = ap.parse_args(list(argv))
    mods = {"tree": _load_op(os.path.join(os.path.dirname(__file__), ".."),
                             "paged_attention", "paged_tree")}
    mods.update(map(_load_paged, a.parent))
    if a.parity:
        _paged_parity(mods, a.kind, a.tile)
    for kname in a.kind:
        kind = PAGED_KINDS[kname]
        wide = "v" in kind or kind.get("sel")
        contexts = PAGED_CONTEXTS + (PAGED_LONG if wide else ())
        for height in a.tile:
            if kind.get("sel") and height == "row":
                continue             # its one-row tile takes no selection
            ops = _paged_operands(kind, height, max(contexts))
            sel = _paged_selection(kind, height, max(contexts))
            steps, failed = {}, {}
            shapes = ops + _paged_tiles(kind, height, 1)
            for tag, mod in mods.items():
                try:
                    steps[tag] = _paged_step(
                        mod, f"{tag}_{height}", kind, height,
                        sel).lower(*shapes).compile()
                except Exception as e:             # e.g. over the VMEM limit
                    failed[tag] = str(e).splitlines()[0][:160]
            # (row name, module's tag, every other tile dead)
            plan = [(f"{tag}_{height}", tag, False) for tag in steps]
            if a.gaps and "tree" in steps:
                plan.append((f"tree_{height}_gaps", "tree", True))
            us = {name: {} for name, _tag, _gaps in plan}
            for blocks in contexts:
                for gaps in (False, True):
                    # by the program's own name, which the trace knows
                    run = {f"{tag}_{height}": (name, steps[tag])
                           for name, tag, g in plan if g == gaps}
                    if not run:
                        continue
                    rows = _traced_kernels(
                        {prog: step for prog, (_name, step) in run.items()},
                        ops + _paged_tiles(kind, height, blocks, gaps),
                        kernel_of=lambda text: "kernel")
                    live = PAGED_TILES[height] // (2 if gaps else 1)
                    for prog, row in rows.items():
                        if "kernel" in row:
                            us[run[prog][0]][blocks] = \
                                1e3 * row["kernel"] / live
            out = {}
            for name, by in us.items():
                if len(by) < 2:
                    continue
                b, fixed = np.polyfit(list(by), list(by.values()), 1)
                out[name] = {
                    "us_a_tile": {n: round(t, 3) for n, t in by.items()},
                    "a_us": round(float(fixed), 3),
                    "b_us_a_block": round(float(b), 3),
                    "peak_pct": {n: round(
                        100 * n * PAGED_BLOCK * _paged_lane_bytes(kind)
                        / V5E_HBM / (t * 1e-6), 1) for n, t in by.items()}}
            emit("paged", kind=kname, tile=height, shape=kind,
                 tiles_a_call=PAGED_TILES[height],
                 block_bytes=PAGED_BLOCK * _paged_lane_bytes(kind),
                 rows=out, failed=failed)


# ``brumby-rollout-sat``'s decode step: 16 rows on 16 slots + the sink, 8
# layers, 40 query heads over 8 of 128: a head's state [128, 8320] float32
RET_CELL = dict(layers=8, slots=17, rows=16, kv_heads=8, group=5,
                head_dim=128)
# features of the block a grid step of PR 49's kernel copies (its
# ``STEP_FEATURES``): its own tile, the whole head
RET_BLOCKS = (1664, 8320)
# (COMPUTE_ROWS, COMPUTE_DIAGONALS) of the one-pass walk, under ``--tiles``
RET_TILES = ((32, 1), (64, 1), (128, 1), (32, 5), (64, 5), (128, 5), (32, 13),
             (64, 13), (128, 13))
# WRITE_LANES, the lanes of one of the copies a head goes out in, under
# ``--copies``: 1, 5, 13, 65 copies of the whole head
RET_COPIES = (8320, 1664, 640, 128)
RET_PARITY_STEPS = 6


def _load_retention(root):
    """``ops/retention.py`` beside the tree's: named into the tree's package,
    where its two relative imports resolve."""
    tag = os.path.basename(os.path.abspath(root)).strip("_")
    return tag, _load_op(root, "retention",
                         "deepspeedsyclsupport_tpu.ops.retention_" + tag)


def _ret_update_only(x_ref, st_ref, y_ref, group):
    """An update in place with NO read-out and no features (in the place of
    the tree's ``_walk``): what the bytes of a whole head cost by
    themselves."""
    del x_ref, group
    st_ref[...] = st_ref[...] * 0.99 + 0.01
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def _ret_operands(seed=0):
    """The pool and ``(decay, qs, k, v, slots)`` of one decode step."""
    c = RET_CELL
    from deepspeedsyclsupport_tpu.ops import retention

    d, hk, rows = c["head_dim"], c["kv_heads"], c["rows"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    qs = jax.random.normal(ks[0], (rows, hk, c["group"], d)) * d ** -0.5
    k, v = jax.random.normal(ks[1], (2, rows, hk, d))
    decay = jnp.exp(-jnp.abs(jax.random.normal(ks[2], (rows, hk))) * 0.01)
    pool = jax.jit(lambda key: 0.1 * jax.random.normal(
        key, (c["layers"], c["slots"], hk, d, retention.state_dim(d))))(ks[3])
    return pool, (decay, qs, k, v, jnp.arange(rows, dtype=jnp.int32))


def _ret_step(mod, name, expanded=False):
    """Every layer's state step of ``mod`` in one program named ``name``,
    the pool donated. ``expanded``: the module takes the features of the
    queries and the key (PR 49's), not the rows themselves."""
    def step(pool, decay, qs, k, v, slots):
        if expanded:
            qs, k = mod.phi(qs), mod.phi(k)

        def layer(i, c):
            pool, acc = c
            y, pool = mod._state_step_pallas(pool, i, slots, decay, qs, k, v)
            return pool, acc + y
        return jax.lax.fori_loop(
            0, RET_CELL["layers"], layer, (pool, jnp.zeros(qs.shape[:3]
                                                          + v.shape[-1:])))
    step.__name__ = name
    return jax.jit(step, donate_argnums=0)


def _ret_parity(tree, pool, args, steps=RET_PARITY_STEPS):
    """The tree's kernel against the XLA form ON THE CHIP (where its copies
    run beside one another; the interpreter's run one after another):
    ``steps`` steps one after another on one layer's pool, the rows dealt
    other slots at every step and two of them the sink; as the cell runs it
    (a head out in thirteen copies) and with a head out in ONE copy, as a
    width ``WRITE_LANES`` does not divide goes."""
    decay, qs, k, v, slots = args
    sink = RET_CELL["slots"] - 1
    slots = slots.at[jnp.asarray([3, 11])].set(sink)
    live = slots != sink

    def run(step):
        def one(t, c):
            pool, acc = c
            turn = lambda a: jnp.roll(a, t, axis=0)      # noqa: E731
            y, pool = step(pool, 0, jnp.roll(slots, 3 * t), turn(decay),
                           turn(qs), turn(k), turn(v))
            y = jnp.where(jnp.roll(live, 3 * t)[:, None, None, None], y, 0)
            return pool, acc + y * (1 + t)
        return jax.jit(lambda pool: jax.lax.fori_loop(
            0, steps, one, (pool, jnp.zeros(qs.shape[:3] + v.shape[-1:]))))(
                pool)

    want_pool, want = run(tree.STATE_STEPS["xla"])
    for name, lanes in (("cell", tree.WRITE_LANES),
                        ("one_copy_out", pool.shape[-1])):
        kept, tree.WRITE_LANES = tree.WRITE_LANES, lanes
        try:
            got_pool, got = run(tree.STATE_STEPS["pallas"])
        finally:
            tree.WRITE_LANES = kept
        emit("retention_parity", out=name, steps=steps,
             y_max=float(jnp.max(jnp.abs(want))),
             y_err=float(jnp.max(jnp.abs(want - got))),
             pool_max=float(jnp.max(jnp.abs(want_pool[:, :sink]))),
             pool_err=float(jnp.max(jnp.abs(
                 want_pool[:, :sink] - got_pool[:, :sink]))))
        del got_pool, got


def retention(argv=()):
    """The state step's kernel ALONE at the cell's shape, its device time
    read off a profiler trace: ``update`` (the tree's copies with no
    read-out: the bytes' own floor) and ``one_pass`` (the tree's); for each
    ``--parent DIR`` its module beside them (``as_is``: PR 49's kernel at
    the block of 1,664 features and at the whole head's 8,320, with
    ``steps x a + bytes x c`` from the two; a later tree's as it stands);
    ``--tiles`` adds the tree's walk at other compute tiles, ``--copies``
    the whole head sent out in other numbers of copies, ``--parity`` holds
    the tree's kernel against the XLA form on the chip over several steps.
    Each row: ms a layer, us a (row, head), the share of 819 GB/s (the state
    read once and written once), the XLA operations beside the kernel."""
    import argparse

    from deepspeedsyclsupport_tpu.ops import retention as tree

    ap = argparse.ArgumentParser(prog="tpu_tune.py retention")
    ap.add_argument("--parent", action="append", default=[])
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--copies", action="store_true")
    ap.add_argument("--parity", action="store_true")
    a = ap.parse_args(list(argv))
    c = RET_CELL
    d, dim = c["head_dim"], tree.state_dim(c["head_dim"])
    heads = c["rows"] * c["kv_heads"]
    moved = 2 * heads * d * dim * 4
    pool, args = _ret_operands()
    if a.parity:
        _ret_parity(tree, pool[:1], args)      # one layer's pool, held thrice
    # (row name, features a grid step copies, module, {attribute: value
    # while the row's program is traced})
    plan, fitted = [], []
    for tag, mod in map(_load_retention, a.parent):
        if hasattr(mod, "STEP_FEATURES"):
            plan += [(f"{tag}_as_is_{block}", block, mod,
                      {"STEP_FEATURES": block}) for block in RET_BLOCKS]
            fitted.append(f"{tag}_as_is")
        else:
            plan.append((f"{tag}_as_is_{dim}", dim, mod, {}))
    plan += [(f"update_{dim}", dim, tree, {"_walk": _ret_update_only}),
             (f"one_pass_{dim}", dim, tree, {})]
    if a.tiles:
        plan += [(f"one_pass_{dim}_tile{r}x{w}", dim, tree,
                  {"COMPUTE_ROWS": r, "COMPUTE_DIAGONALS": w})
                 for r, w in RET_TILES
                 if (r, w) != (tree.COMPUTE_ROWS, tree.COMPUTE_DIAGONALS)]
    if a.copies:
        plan += [(f"one_pass_{dim}_out{dim // n}", dim, tree,
                  {"WRITE_LANES": n})
                 for n in RET_COPIES if n != tree.WRITE_LANES]
    steps, failed = {}, {}
    for name, _block, mod, patch in plan:
        kept = {k: getattr(mod, k) for k in patch}
        for k, val in patch.items():
            setattr(mod, k, val)
        try:
            steps[name] = _ret_step(
                mod, name, hasattr(mod, "STEP_FEATURES")).lower(
                    pool, *args).compile()
        except Exception as e:                     # e.g. over the VMEM limit
            failed[name] = str(e).splitlines()[0][:160]
        finally:
            for k, val in kept.items():
                setattr(mod, k, val)
    rows = _traced_kernels(steps, args, kernel_of=lambda text: "kernel",
                           carry=pool)
    for name, block, _mod, _patch in plan:
        row = rows.get(name, {})
        if "kernel" not in row:
            continue
        row["steps"] = heads * (dim // block)
        row["us_per_row_head"] = round(1e3 * row["kernel"] / heads, 3)
        row["peak_pct"] = round(100 * moved / V5E_HBM
                                / (row["kernel"] * 1e-3), 1)
    fits = {}
    for body in fitted:
        two = [rows.get(f"{body}_{b}", {}) for b in RET_BLOCKS]
        if all("kernel" in r for r in two):
            per_step = 1e3 * (two[0]["kernel"] - two[1]["kernel"]) \
                / (two[0]["steps"] - two[1]["steps"])
            rest = 1e-3 * (two[1]["kernel"] - 1e-3 * per_step
                           * two[1]["steps"])
            fits[body] = {"a_us_per_step": round(per_step, 3),
                          "c_peak_pct": round(100 * moved / V5E_HBM / rest,
                                              1)}
    emit("retention", cell=RET_CELL, bytes_a_layer=moved, rows=rows,
         fits=fits, failed=failed)


# ``keye-video-sat``'s indexer: 8 sequences, block tables of 768 x 64 keys,
# 16 indexer heads of 64, the 2,048 best kept; an atom of 128 rows
DSA_CELL = dict(seqs=8, table=49152, heads=16, dim=64, topk=2048, atom=128)
DSA_CONTEXTS = (16384, 32768, 49152)
# keys a grid step of the scores kernel takes under a one-row tile
DSA_KEYS = (512, 1024, 2048, 4096, 8192, 16384)


def _dsa_operands(rows, seed=0):
    """``(qI [8, rows, 16, 64], w [8, rows, 16], keys [8, 49152, 64], scores
    [8, rows, 49152])`` of 8 tiles of ``rows`` rows, one a sequence."""
    c = DSA_CELL
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (c["seqs"], rows, c["heads"])
    q = jax.random.normal(ks[0], shape + (c["dim"],), jnp.bfloat16)
    w = jax.random.normal(ks[1], shape, jnp.float32)
    k = jax.jit(lambda key: jax.random.normal(
        key, (c["seqs"], c["table"], c["dim"]), jnp.bfloat16))(ks[2])
    scores = jax.jit(lambda key: jax.random.normal(
        key, (c["seqs"], rows, c["table"]), jnp.float32))(ks[3])
    return q, w, k, scores


def _named(name, fn, tag):
    """``fn`` as a program called ``name`` that also returns the number
    ``tag``: two steps that are one computation but for the name (the
    tree's rule and a candidate; an atom's scores in either module) would
    share one executable, name and all, and the trace would count both
    under the first."""
    def step(*args):
        return fn(*args), jnp.int32(tag)
    step.__name__ = name
    return jax.jit(step)


def _dsa_rows_steps(mods, candidates):
    """{name: (step, the keys a scores step takes while it is traced or
    None: the rule's)} of the one-token rows' route over ``(qI, w, keys,
    scores [8, 1, C], lens [8])``: the scores of 8 one-row tiles (the tree's
    at each keys-a-step candidate, a parent's as it stands), the selection
    of ONE 8-row tile, the positions out of a mask, and PR 45's route
    (float32 scores and ``lax.top_k`` in XLA)."""
    c = DSA_CELL
    tree = mods["tree"]
    seq = jnp.arange(c["seqs"])
    steps = {}

    def scores_of(mod):
        return lambda q, w, k, s, lens: mod.index_scores_pallas(
            q, w, k, seq, lens, scale=0.03125)

    for keys in candidates:
        steps[f"rows_scores_{keys}"] = (scores_of(tree), keys)
    for tag, mod in mods.items():
        steps[f"rows_scores_{tag}"] = (scores_of(mod), None)
    steps["rows_select_tree"] = (
        lambda q, w, k, s, lens: tree.select_topk_pallas(
            s.reshape(1, c["seqs"], -1), (lens - 1)[None],
            jnp.full((1,), c["seqs"]), k=c["topk"]), None)
    # (about 2,000 of a row's first 16 k set: what a selection leaves)
    steps["rows_positions_tree"] = (
        lambda q, w, k, s, lens: tree.positions_from_mask(
            (s[:, 0] > 1.16) & (jnp.arange(c["table"])[None]
                                < lens[:, None]), k=c["topk"]), None)

    def old_route(q, w, k, s, lens):
        scores = tree.index_scores_reference(q, w, k, seq,
                                             scale=0.03125)[:, 0]
        seen = jnp.arange(c["table"])[None] < lens[:, None]
        return jax.lax.top_k(jnp.where(seen, scores, -jnp.inf),
                             c["topk"])[1]
    steps["rows_xla_scores_and_top_k"] = (old_route, None)
    return steps


def _dsa_atom_steps(mods):
    """:func:`_dsa_rows_steps` of the 128-row atom's tile, 8 a call, over
    ``(qI, w, keys, scores [8, 128, C], hi [8])``: each module's scores and
    selection kernel."""
    c = DSA_CELL
    seq = jnp.arange(c["seqs"])
    steps = {}
    for tag, mod in mods.items():
        steps[f"atom_scores_{tag}"] = (
            lambda q, w, k, s, hi, mod=mod: mod.index_scores_pallas(
                q, w, k, seq, hi, scale=0.03125), None)
        steps[f"atom_select_{tag}"] = (
            lambda q, w, k, s, hi, mod=mod: mod.select_topk_pallas(
                s, hi - c["atom"], jnp.full_like(hi, c["atom"]),
                k=c["topk"]), None)
    return steps


def _dsa_parity(tree):
    """The tree's kernels against their ``jax.numpy`` twins ON THE CHIP at
    the cell's shapes: the scores of one-row tiles (worst difference over
    the twin's largest score; bf16 products summed in another order), the
    selection of an 8-row tile whose rows have lengths of their own (a dead
    row, one under ``topk``, ties planted at the k-th value) mask for mask,
    and the positions against numpy's."""
    c = DSA_CELL
    q, w, k, scores = _dsa_operands(1, seed=3)
    table, topk = c["table"], c["topk"]
    lens = jnp.asarray([table, 0, topk * 3 // 4, table // 3 + 1,
                        table * 2 // 3 + 232, topk, table - 2151,
                        table * 2 // 5][:c["seqs"]], jnp.int32)
    seq = jnp.arange(c["seqs"])
    want = jax.jit(lambda: tree.index_scores_reference(
        q, w, k, seq, scale=0.03125))()
    got = jax.jit(lambda: tree.index_scores_pallas(
        q, w, k, seq, lens, scale=0.03125))()
    seen = np.arange(c["table"])[None] < np.asarray(lens)[:, None]
    emit("dsa_parity", what="rows_scores", keys_a_step=tree.score_keys(
        1, c["heads"], c["dim"], 2, c["table"]),
        worst=float(np.abs(np.where(seen, np.asarray(got - want)[:, 0], 0))
                    .max()), largest=float(jnp.max(jnp.abs(want))))
    # a few values only: the k-th is tied in every row
    tied = jnp.round(scores[:, 0] * 2).clip(-2, 3)
    for name, s in (("rows_select", scores[:, 0]),
                    ("rows_select_ties", tied)):
        args = (s[None], (lens - 1)[None], jnp.full((1,), c["seqs"]))
        want = np.asarray(jax.jit(lambda: tree.select_topk_reference(
            *args, k=c["topk"]))())
        got = jax.jit(lambda: tree.select_topk_pallas(*args,
                                                      k=c["topk"]))()
        pos = np.asarray(jax.jit(lambda: tree.positions_from_mask(
            got[0], k=c["topk"]))())
        held = [np.flatnonzero(row)[:c["topk"]] for row in want[0]]
        emit("dsa_parity", what=name,
             kept=[int(row.sum()) for row in want[0]],
             mask_differs=int(np.count_nonzero(np.asarray(got) != want)),
             positions_differ=int(sum(
                 p.tolist() != h.tolist() + [c["table"]] * (c["topk"]
                                                            - len(h))
                 for p, h in zip(pos, held))))


def _dsa_sweep(mods, candidates):
    """:func:`dsa`'s two tables: every step of the rows' route and of the
    atom's tile compiled once, then traced at each context."""
    c, tree = DSA_CELL, mods["tree"]
    for tile, rows, plan in (
            ("rows", 1, _dsa_rows_steps(mods, candidates)),
            ("atom", c["atom"], _dsa_atom_steps(mods))):
        ops = _dsa_operands(rows)
        lens = jnp.full((c["seqs"],), c["table"], jnp.int32)
        steps, failed = {}, {}
        for tag, (name, (step, keys)) in enumerate(plan.items()):
            kept = tree.score_keys
            if keys:
                tree.score_keys = lambda *_a, keys=keys: keys
            try:
                steps[name] = _named(name, step, tag).lower(
                    *ops, lens).compile()
            except Exception as e:                 # e.g. over the VMEM limit
                failed[name] = str(e).splitlines()[0][:160]
            finally:
                tree.score_keys = kept
        us, xla = ({name: {} for name in steps} for _ in range(2))
        for ctx in DSA_CONTEXTS:
            traced = _traced_kernels(
                steps, ops + (jnp.full((c["seqs"],), ctx, jnp.int32),),
                kernel_of=lambda text: "kernel")
            for name, row in traced.items():
                us[name][ctx] = round(1e3 * row.get("kernel", 0.0), 2)
                xla[name][ctx] = round(1e3 * row["xla"], 2)
        emit("dsa", tile=tile, cell=c, us_a_call=us, xla_us_a_call=xla,
             failed=failed,
             keys_a_step={tag: mod.score_keys(rows, c["heads"], c["dim"], 2,
                                              c["table"])
                          for tag, mod in mods.items()
                          if hasattr(mod, "score_keys")})


def dsa(argv=()):
    """The indexer's kernels ALONE at ``keye-video-sat``'s shapes, their
    device time read off a profiler trace, at contexts of 16 k, 32 k and
    48 k in every row: us a call of the one-token rows' steps (8 rows: one
    layer of a forward) and of the atom's two kernels (8 atoms a call), the
    tree's module beside ``--parent DIR``'s; ``xla_us_a_call``: the XLA
    operations beside the kernel (a step with no kernel is all there; under
    a scores kernel it is the copy that lays the keys out for it, which a
    forward's gather does on the way). ``--keys``: the keys-a-step
    candidates of the one-row scores (the rule's own choice is the row
    ``rows_scores_tree``). ``--parity``: the tree's kernels against the
    twins on the chip."""
    import argparse

    ap = argparse.ArgumentParser(prog="tpu_tune.py dsa")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--keys", type=int, nargs="*", default=list(DSA_KEYS))
    ap.add_argument("--parity", action="store_true")
    a = ap.parse_args(list(argv))
    mods = {"tree": _load_op(os.path.join(os.path.dirname(__file__), ".."),
                             "sparse_index", "sparse_index_tree")}
    if a.parent:
        mods["parent"] = _load_op(a.parent, "sparse_index",
                                  "sparse_index_parent")
    if a.parity:
        _dsa_parity(mods["tree"])
    _dsa_sweep(mods, a.keys)


# ``solar2-agent-sat``'s decode step: 256 rows on 256 slots + the sink, 3
# delta-rule layers, 64 heads of a [128, 128] float32 state; pieces of 64
KDA_CELL = dict(layers=3, slots=257, rows=256, heads=64, dim=128, chunk=64)
KDA_HEADS = (8, 16, 32, 64)     # heads a grid step of the state step takes
KDA_PIECES = 8                  # pieces the chunked form's program runs
KDA_CHUNK_PIECES = 12           # ... and a 768-row chunk's, all of ONE slot
KDA_PIECE_HEADS = (8, 16, 32, 64)   # heads a grid step of the pieces' kernel


def _kda_rows(rows, seed=0, strong=False):
    """``(q, k, v, g, beta)`` of ``rows`` rows as the model hands them over:
    unit keys, scaled unit queries, log-decays from under a token's
    half-life to hundreds (``strong``: -40 a row on a quarter of the
    channels, so that a piece's running sum passes float32's exponent)."""
    from deepspeedsyclsupport_tpu.ops.kda import l2norm

    c = KDA_CELL
    h, d = c["heads"], c["dim"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = jax.random.normal(ks[0], (3, rows, h, d))
    g = -jnp.exp(jax.random.uniform(ks[1], (rows, h, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    if strong:
        g = jnp.where(jnp.arange(d) % 4 == 0, -40.0, g)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[2], (rows, h)))
    return l2norm(q) * d ** -0.5, l2norm(k), v, g, beta


def _kda_parity(tree):
    """Both entries against the sequential recurrence (the XLA state step a
    token at a time) on the chip: the Pallas step over several steps with
    two rows on the sink and one fresh; the chunked form over three pieces
    of two sequences, one ragged, under a plain and a strong decay."""
    import types

    c = KDA_CELL
    h, d, q = c["heads"], c["dim"], c["chunk"]
    cfg = types.SimpleNamespace(kda_chunk_size=q)
    rows, slots_n = 16, 17
    pool = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                   (1, slots_n, h, d, d))
    slots = jnp.arange(rows, dtype=jnp.int32).at[jnp.asarray([3, 11])].set(
        slots_n - 1)
    fresh = jnp.zeros((rows,), bool).at[5].set(True)
    live = slots != slots_n - 1
    got = {}
    for name in ("xla", "pallas"):
        p, acc = pool, 0.0
        for t in range(3):
            args = _kda_rows(rows, seed=t)
            y, p = jax.jit(lambda p, *a, name=name: tree.decode_step(
                *a, p, 0, slots, fresh, cfg, tree.STATE_STEPS[name]))(
                    p, *args)
            acc = acc + jnp.where(live[:, None, None], y, 0) * (1 + t)
        got[name] = (acc, p[:, :slots_n - 1])
    emit("kda_parity", entry="decode_step", steps=3,
         y_max=float(jnp.max(jnp.abs(got["xla"][0]))),
         y_err=float(jnp.max(jnp.abs(got["xla"][0] - got["pallas"][0]))),
         pool_max=float(jnp.max(jnp.abs(got["xla"][1]))),
         pool_err=float(jnp.max(jnp.abs(got["xla"][1] - got["pallas"][1]))))
    # five pieces: sequence A in slot 2 (64 rows fresh), three consecutive
    # pieces of sequence B in slot 0 (64, 64 and 21 rows from what the slot
    # holds: the state is handed on), then A's next 64 rows
    tail = q // 3
    t = 4 * q + tail
    pieces = (jnp.asarray([0, q, 2 * q, 3 * q, 3 * q + tail, 0]),
              jnp.asarray([q, q, q, tail, q, 0]),
              jnp.asarray([2, 0, 0, 0, 2, slots_n - 1]),
              jnp.asarray([True, False, False, False, False, False]),
              jnp.asarray(5))
    for strong in (False, True):
        args = _kda_rows(t, seed=9, strong=strong)

        def token(i, carry):
            p, out = carry
            slot = jnp.where((i < q) | (i >= 3 * q + tail), 2, 0)
            y_i, p = tree.decode_step(
                *(jax.lax.dynamic_slice_in_dim(a, i, 1) for a in args), p, 0,
                slot[None], (i == 0)[None], cfg, tree.STATE_STEPS["xla"])
            return p, jax.lax.dynamic_update_slice_in_dim(out, y_i, i, 0)

        p_seq, y_seq = jax.jit(lambda p: jax.lax.fori_loop(
            0, t, token, (p, jnp.zeros(args[2].shape))))(pool)
        for name in ("xla", "pallas"):
            y, p = jax.jit(lambda p, *a, name=name: tree.chunked(
                *a, p, 0, pieces, cfg, tree.PIECES[name]))(pool, *args)
            emit("kda_parity", entry="chunked", form=name,
                 strong_decay=strong, rows=t,
                 y_max=float(jnp.max(jnp.abs(y_seq))),
                 y_err=float(jnp.max(jnp.abs(y - y_seq))),
                 pool_max=float(jnp.max(jnp.abs(p_seq[:, :3]))),
                 pool_err=float(jnp.max(jnp.abs(p[:, :3] - p_seq[:, :3]))),
                 finite=bool(jnp.isfinite(y).all()))


def kda(argv=()):
    """The delta rule's two entries ALONE at the cell's shape, their device
    time read off a profiler trace: the state step's kernel at each of
    ``--heads`` heads a grid step (ms a layer, the share of 819 GB/s: every
    row's state read once and written once) and the chunked form over
    ``KDA_PIECES`` full pieces of one layer and over a chunk of
    ``KDA_CHUNK_PIECES`` of one slot, as the XLA loop and as the kernel at
    each of ``--piece-heads`` (ms a piece); ``--parity`` holds both entries
    against the sequential recurrence first."""
    import argparse
    import types

    from deepspeedsyclsupport_tpu.ops import kda as tree

    ap = argparse.ArgumentParser(prog="tpu_tune.py kda")
    ap.add_argument("--heads", type=int, nargs="*", default=list(KDA_HEADS))
    ap.add_argument("--piece-heads", type=int, nargs="*",
                    default=list(KDA_PIECE_HEADS))
    ap.add_argument("--parity", action="store_true")
    a = ap.parse_args(list(argv))
    c = KDA_CELL
    h, d, rows, q = c["heads"], c["dim"], c["rows"], c["chunk"]
    cfg = types.SimpleNamespace(kda_chunk_size=q)
    if a.parity:
        _kda_parity(tree)
    pool = jax.jit(lambda key: 0.1 * jax.random.normal(
        key, (c["layers"], c["slots"], h, d, d)))(jax.random.PRNGKey(3))
    args = _kda_rows(rows) + (jnp.arange(rows, dtype=jnp.int32),
                              jnp.ones((rows,), jnp.float32))

    def step_of(name, hb):
        def step(pool, q_, k_, v_, g_, beta_, slots, keep):
            def layer(i, carry):
                pool, acc = carry
                y, pool = tree._state_step_pallas(
                    pool, i, slots, keep, q_, k_, v_, g_, beta_, heads=hb)
                return pool, acc + y
            return jax.lax.fori_loop(0, c["layers"], layer,
                                     (pool, jnp.zeros(v_.shape)))
        step.__name__ = name
        return jax.jit(step, donate_argnums=0)

    steps, failed = {}, {}
    for hb in a.heads:
        name = f"state_step_{hb}"
        try:
            steps[name] = step_of(name, hb).lower(pool, *args).compile()
        except Exception as e:                     # e.g. over the VMEM limit
            failed[name] = str(e).splitlines()[0][:160]
    moved = 2 * rows * h * d * d * 4
    out = _traced_kernels(steps, args, kernel_of=lambda text: "kernel",
                          carry=pool)
    for row in out.values():
        if "kernel" in row:
            row["peak_pct"] = round(100 * moved / V5E_HBM
                                    / (row["kernel"] * 1e-3), 1)
    emit("kda", cell=c, bytes_a_layer=moved, rows=out, failed=failed)
    del steps
    # the chunked form: KDA_PIECES full pieces of as many sequences through
    # the XLA loop and through the kernel at each of --piece-heads heads a
    # grid step; then a 768-row chunk, twelve pieces of ONE slot (the state
    # stays in VMEM from piece to piece)
    def pieces_of(n, one_slot):
        slot = jnp.zeros((n,), jnp.int32) if one_slot else jnp.arange(n)
        return (jnp.arange(n) * q, jnp.full((n,), q), slot,
                jnp.zeros((n,), bool), jnp.asarray(n))

    def chunk_of(name, form, pieces):
        def chunk(pool, *rows_):
            y, pool = tree.chunked(*rows_, pool, 1, pieces, cfg, form)
            return pool, y
        chunk.__name__ = name
        return jax.jit(chunk, donate_argnums=0)

    state, row = h * d * d * 4, q * h * d * 4
    for n, one_slot in ((KDA_PIECES, False), (KDA_CHUNK_PIECES, True)):
        pool = jax.jit(lambda key: 0.1 * jax.random.normal(
            key, (c["layers"], c["slots"], h, d, d)))(jax.random.PRNGKey(3))
        rows_t = _kda_rows(n * q, seed=1)
        forms = {"chunked_xla": tree.PIECES["xla"]}
        forms.update({f"kda_piece_{hb}": functools.partial(
            tree.PIECES["pallas"], heads=hb) for hb in a.piece_heads})
        prog, failed = {}, {}
        for name, form in forms.items():
            try:
                prog[name] = chunk_of(name, form, pieces_of(n, one_slot)) \
                    .lower(pool, *rows_t).compile()
            except Exception as e:
                failed[name] = str(e).splitlines()[0][:160]
        out = _traced_kernels(prog, rows_t, kernel_of=lambda text: "kernel",
                              carry=pool)
        # what a piece has to move: q, k, g, v in and y out, its state in
        # and out (once a SLOT where the kernel carries it)
        moved = n * 5 * row + 2 * (1 if one_slot else n) * state
        for name, r in out.items():
            ms = r.get("kernel", 0.0) + r["xla"]
            r.update(ms=round(ms, 4), ms_a_piece=round(ms / n, 4),
                     peak_pct=round(100 * moved / V5E_HBM / (ms * 1e-3), 1),
                     temp_mib=round(prog[name].memory_analysis()
                                    .temp_size_in_bytes / 2**20, 1))
        emit("kda_chunked", pieces=n, slots=1 if one_slot else n,
             rows_a_piece=q, bytes_moved=moved, rows=out, failed=failed)
        del prog


# The one-token rows' convolution of the two cells that run it: every slot
# but the sink live, the rows dealt their slots in no order
CONV_CELLS = {
    "solar2-agent-sat": dict(layers=3, slots=257, rows=256, channels=24576,
                             bias=False),
    "nemo3-reason-sat": dict(layers=12, slots=129, rows=128, channels=6144,
                             bias=True),
}
# ... and the pieces': a mixed round's rows, a piece's rows at most, the most
# pieces a forward can hold (``ragged.tile_places``)
CONV_MIXED = {"solar2-agent-sat": dict(tokens=768, chunk=64, most=269),
              "nemo3-reason-sat": dict(tokens=512, chunk=128, most=133),
              }
CONV_PIECE_CHANNELS = (0,)      # channels a grid step (0: the rule's own)
CONV_PIECE_STRIPS = (0,)        # lanes a strip (0: the rule's own)
CONV_TAPS = 4
CONV_SLOTS = (16, 32, 64)       # slots a grid step of the tail's kernel takes
CONV_LANES = (2048, 4096, 8192)             # ... and channels
CONV_PARITY_STEPS = 3


def _conv_pool(c, seed=0):
    """The cell's bfloat16 pool, drawn on the device."""
    return jax.jit(lambda key: jax.random.normal(
        key, (c["layers"], CONV_TAPS - 1, c["slots"], c["channels"]),
        jnp.bfloat16))(jax.random.PRNGKey(seed))


def _conv_rows(c, seed=0, step=0):
    """``(x, w, bias, slots, keep)`` of one step: a slot a row in a shuffled
    order, every 11th row the sink's, every 7th from zeros."""
    rows, ch = c["rows"], c["channels"]
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    x = jax.random.normal(jax.random.fold_in(ks[0], step), (rows, ch),
                          jnp.bfloat16)
    w = 0.5 * jax.random.normal(ks[1], (CONV_TAPS, ch))
    bias = jax.random.normal(ks[2], (ch,)) if c["bias"] else None
    order = jax.random.permutation(jax.random.fold_in(ks[0], 100 + step),
                                   c["slots"] - 1)[:rows]
    at = jnp.arange(rows)
    slots = jnp.where(at % 11 == 10, c["slots"] - 1, order).astype(jnp.int32)
    return x, w, bias, slots, at % 7 != 6


def _conv_program(name, fn, c, tag=0):
    """Every layer's convolution step ``fn(x, w, bias, pool, layer, slots,
    keep)`` in one program named ``name``, the pool donated; the results are
    summed (an add of ``[rows, channels]`` float32 a layer among the XLA
    operations of every row alike) from ``tag``: two rows whose programs
    read the same would share ONE executable of the compile cache, and the
    trace would name it once."""
    def step(pool, x, w, bias, slots, keep):
        def layer(i, carry):
            pool, acc = carry
            out, pool = fn(x, w, bias, pool, i, slots, keep)
            return pool, acc + out
        return jax.lax.fori_loop(
            0, c["layers"], layer,
            (pool, jnp.full(x.shape, float(tag), jnp.float32)))
    step.__name__ = name
    return jax.jit(step, donate_argnums=0)


def _conv_parity(tree, cell, c):
    """The tail's kernel against the XLA form ON THE CHIP at the cell's
    shape: ``CONV_PARITY_STEPS`` steps one after another on every layer, the
    rows dealt other slots at every step. The results of the rows on a slot
    of their own and every slot's tail but the sink's."""
    got = {}
    for name in ("xla", "pallas"):
        pool = _conv_pool(c)
        prog = _conv_program(f"parity_{name}", tree.CONV_STEPS[name], c)
        outs = []
        for t in range(CONV_PARITY_STEPS):
            args = _conv_rows(c, step=t)
            pool, out = prog(pool, *args)
            outs.append(jnp.where((args[3] != c["slots"] - 1)[:, None],
                                  out, 0))
        got[name] = (jnp.stack(outs), pool[:, :, :-1].astype(jnp.float32))
    emit("conv_parity", cell=cell, steps=CONV_PARITY_STEPS,
         out_max=float(jnp.max(jnp.abs(got["xla"][0]))),
         out_err=float(jnp.max(jnp.abs(got["xla"][0] - got["pallas"][0]))),
         tails_differ=int(jnp.sum(got["xla"][1] != got["pallas"][1])))


def _conv_piece_rows(c, t, seed=0):
    """``(x [t, channels] bfloat16, w, bias)`` of a mixed round."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    ch = c["channels"]
    return (jax.random.normal(ks[0], (t, ch), jnp.bfloat16),
            0.5 * jax.random.normal(ks[1], (CONV_TAPS, ch)),
            jax.random.normal(ks[2], (ch,)) if c["bias"] else None)


def _conv_piece_table(rows):
    """``[(row0, length, slot, fresh)]`` as ``conv_pieces`` takes them."""
    row0, length, slot, fresh = (jnp.asarray(v) for v in zip(*rows))
    i32 = jnp.int32
    return (row0.astype(i32), length.astype(i32), slot.astype(i32),
            fresh.astype(bool), jnp.asarray(len(rows), i32))


def _conv_piece_layouts(c, m):
    """``{name: pieces}`` of a mixed round's ``tokens`` rows: eight whole
    pieces of eight slots a block apart; the round as ONE slot's chunk;
    and a ragged round as the agents' traffic sends it (prompts of 11 to
    ``chunk + 37`` rows on neighbouring slots, a one-token row between
    them, no piece's first row on a tile's)."""
    q, t, last = m["chunk"], m["tokens"], c["slots"] - 1
    table = _conv_piece_table
    eight = [(i * q, q, (i * 37) % last, i % 2 == 0)
             for i in range(min(8, t // q))]
    one = [(i * q, q, 5, i == 0) for i in range(t // q)]
    ragged, row, slot = [], 3, 17
    for n in (q + 37, 11, q, 2 * q - 9, 29, q + 1, 5):
        if row + n > t:
            break
        for k in range(0, n, q):
            ragged.append((row + k, min(q, n - k), slot, k == 0
                           and slot % 2 == 1))
        row, slot = row + n + 1, slot + (1 if slot % 3 else 14)
    return {"eight_slots": table(eight), "one_slot": table(one),
            "ragged": table(ragged)}


def _conv_whole(x, w, bias, pieces, pool, layer, dtype):
    """The plain convolution of each whole sequence the ``pieces`` cut:
    ``(out [T, channels] float32, zero where no piece lies; {slot: its
    last taps - 1 inputs})``, a sequence that is not ``fresh`` behind the
    tail its slot holds."""
    row0, length, slot, fresh, count = (np.asarray(v) for v in pieces)
    k = w.shape[0]
    f32 = jnp.float32
    x = x.astype(dtype).astype(f32)
    out = jnp.zeros(x.shape, f32)
    tails = {}
    for i in range(int(count)):
        s = int(slot[i])
        before = tails[s] if s in tails and not fresh[i] else (
            jnp.zeros((k - 1, x.shape[1]), f32) if fresh[i]
            else pool[layer, :, s].astype(f32))
        ext = jnp.concatenate([before, x[row0[i]:row0[i] + length[i]]])
        acc = sum(w[j] * ext[j:j + int(length[i])] for j in range(k))
        out = out.at[row0[i]:row0[i] + length[i]].set(
            jax.nn.silu(acc if bias is None else bias + acc))
        tails[s] = ext[-(k - 1):]
    return out, tails


def _conv_pieces_parity(tree, cell, c, m):
    """Both forms of the pieces' convolution ON THE CHIP at the cell's
    width against the plain convolution of each whole sequence, over the
    ragged round (hand-overs inside a slot and inside a block, pieces of
    one to ``chunk`` rows, a frame that would pass the batch's end): the
    results, the tails the pieces leave and every other slot bit for bit."""
    q, t = m["chunk"], m["tokens"]
    short = [(0, 1, 2, True), (1, 2, 3, False), (3, 3, 4, False),
             (6, q - 1, 40, False), (5 + q, q, 40, False),
             (t - q - 7, q, 41, True), (t - 7, 7, 41, False)]
    tables = {"ragged": _conv_piece_layouts(c, m)["ragged"],
              "short": _conv_piece_table(short)}
    x, w, bias = _conv_piece_rows(c, t, seed=5)
    for name, pieces in tables.items():
        pool = _conv_pool(c, seed=3)
        want, tails = _conv_whole(x, w, bias, pieces, pool, 1, pool.dtype)
        for form in ("xla", "pallas"):
            out, new = jax.jit(lambda pool, form=form: tree.conv_pieces(
                x, w, bias, pool, 1, pieces, q, tree.CONV_PIECES[form]))(
                    pool)
            named = sorted(tails)
            # every slot of layer 1 that no piece names, and the other layers
            moved = (new != pool).at[1, :, jnp.asarray(named)].set(False)
            emit("conv_pieces_parity", cell=cell, layout=name, form=form,
                 pieces=int(pieces[4]), out_max=float(jnp.max(jnp.abs(want))),
                 out_err=float(jnp.max(jnp.abs(out - want))),
                 tails_differ=int(sum(jnp.sum(
                     new[1, :, s].astype(jnp.float32) != tails[s])
                     for s in named)),
                 others_differ=int(jnp.sum(moved)))


def _conv_pieces(a, tree):
    """``conv --pieces``: a layer's convolution of a mixed round's pieces
    ALONE at the two cells' shapes, the XLA loop beside the kernel."""
    for cell in a.cell:
        c, m = CONV_CELLS[cell], CONV_MIXED[cell]
        if a.parity:
            _conv_pieces_parity(tree, cell, c, m)
        q, t, ch = m["chunk"], m["tokens"], c["channels"]
        x, w, bias = _conv_piece_rows(c, t, seed=2)
        plan = {"pieces_xla": tree.CONV_PIECES["xla"],
                "kernel_tree": tree.CONV_PIECES["pallas"]}
        plan.update({
            f"kernel_{n}x{strip}": functools.partial(
                tree.CONV_PIECES["pallas"], channels=n or None,
                lanes=strip or None)
            for n in a.channels for strip in a.strip if n or strip})
        for layout, pieces in _conv_piece_layouts(c, m).items():
            def program(name, form, tag):
                def step(pool, x, w, bias):
                    out, pool = tree.conv_pieces(x, w, bias, pool, 1, pieces,
                                                 q, form)
                    return pool, out + float(tag)
                step.__name__ = f"{layout}_{name}"
                return jax.jit(step, donate_argnums=0)

            pool = _conv_pool(c)
            steps, failed = {}, {}
            for tag, (name, form) in enumerate(plan.items()):
                try:
                    steps[f"{layout}_{name}"] = program(
                        name, form, tag).lower(pool, x, w, bias).compile()
                except Exception as e:             # e.g. over the VMEM limit
                    failed[name] = str(e).splitlines()[0][:160]
            rows = _traced_kernels(steps, (x, w, bias),
                                   kernel_of=lambda text: "kernel",
                                   carry=pool)
            n = int(pieces[4])
            live = int(jnp.sum(pieces[1]))
            # a piece's rows in and its results out, its slot's tail in and
            # out
            moved = live * ch * (2 + 4) + n * 2 * (CONV_TAPS - 1) * ch * 2
            floor_us = 1e6 * moved / V5E_HBM / n
            for row in rows.values():
                # the add of the tag and the zeros out stands on are XLA's
                us = 1e3 * (row.get("kernel", 0.0) + row["xla"]) / n
                row.update(us_a_piece=round(us, 2),
                           ms_a_forward=round(us * m["most"] / 1e3, 3),
                           floor_pct=round(100 * floor_us / us, 1)
                           if us else None)
            emit("conv_pieces", cell=cell, layout=layout, pieces=n,
                 rows_live=live, chunk=q, floor_us_a_piece=round(floor_us, 2),
                 rule=dict(zip(("channels", "strip"), tree.conv_pieces_tile(
                     ch, tree.piece_frame(q), 2, CONV_TAPS))),
                 rows=rows, failed=failed)
            del steps


def conv(argv=()):
    """The one-token rows' convolution ALONE at the two cells' shapes, its
    device time read off a profiler trace, every layer's step in one
    program: ``xla`` (the tree's gather / convolve / scatter), ``--parent
    DIR``'s ``conv_step`` as it stands, the tail's kernel under the rule's
    own constants (``kernel_tree``) and at each of ``--slots`` slots a grid
    step x ``--lanes`` channels a tile. Each row: ms a layer of the kernel
    and of the XLA operations beside it (the token's cast, the row at each
    slot, the sum of the results), and the TAIL's bytes (every row's ``k -
    1`` rows read once and written once) a second of the two together
    against 819 GB/s. ``--parity`` holds the kernel against the XLA form on
    the chip first."""
    import argparse

    from deepspeedsyclsupport_tpu.ops import ssm as tree

    ap = argparse.ArgumentParser(prog="tpu_tune.py conv")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--cell", nargs="*", default=list(CONV_CELLS))
    ap.add_argument("--slots", type=int, nargs="*", default=list(CONV_SLOTS))
    ap.add_argument("--lanes", type=int, nargs="*", default=list(CONV_LANES))
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--pieces", action="store_true")
    ap.add_argument("--channels", type=int, nargs="*",
                    default=list(CONV_PIECE_CHANNELS))
    ap.add_argument("--strip", type=int, nargs="*",
                    default=list(CONV_PIECE_STRIPS))
    a = ap.parse_args(list(argv))
    if a.pieces:
        return _conv_pieces(a, tree)
    parent = a.parent and _load_op(
        a.parent, "ssm", "deepspeedsyclsupport_tpu.ops.ssm_parent")
    for cell in a.cell:
        c = CONV_CELLS[cell]
        if a.parity:
            _conv_parity(tree, cell, c)
        plan = {"xla": tree.CONV_STEPS["xla"],
                "kernel_tree": tree.CONV_STEPS["pallas"]}
        if parent:
            plan["parent"] = parent.conv_step
        plan.update({
            f"kernel_{n}x{lanes}": functools.partial(
                tree.CONV_STEPS["pallas"], slots_a_step=n, lanes=lanes)
            for n in a.slots for lanes in a.lanes})
        pool, args = _conv_pool(c), _conv_rows(c)
        steps, failed = {}, {}
        for tag, (name, fn) in enumerate(plan.items()):
            try:
                steps[name] = _conv_program(name, fn, c, tag).lower(
                    pool, *args).compile()
            except Exception as e:                 # e.g. over the VMEM limit
                failed[name] = str(e).splitlines()[0][:160]
        rows = _traced_kernels(steps, args, kernel_of=lambda text: "kernel",
                               carry=pool)
        moved = 2 * c["rows"] * (CONV_TAPS - 1) * c["channels"] * 2
        for row in rows.values():
            # the kernel's reading is a call's (a layer's), the XLA
            # operations' a whole program's
            ms = row.get("kernel", 0.0) + row["xla"] / c["layers"]
            if not ms:                  # the trace holds nothing of it
                continue
            row["ms_a_layer"] = round(ms, 4)
            row["tail_gb_s"] = round(moved / (ms * 1e-3) / 1e9, 1)
            row["peak_pct"] = round(100 * moved / V5E_HBM / (ms * 1e-3), 1)
        emit("conv", cell=cell, shape=c, tail_bytes_a_layer=moved,
             rule=dict(zip(("slots", "lanes"),
                           tree.conv_tile(c["rows"], c["channels"]))),
             rows=rows, failed=failed)


COMBINE_ROWS = (32, 128, 768)     # tokens a forward


def _serve_configs():
    """``{cell: ModelConfig}`` of every serving cell of ``BENCHMARK.json``,
    off the preset and overrides its configuration's file names."""
    from deepspeedsyclsupport_tpu.models import get_config

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(root, files[w["config"]])) as f:
            cfg = json.load(f)
        if cfg["path"] == "serve":
            out[w["name"]] = get_config(cfg["preset"],
                                        **cfg.get("overrides", {}))
    return out


def _combine_cells():
    """``(k, d, experts, held)`` of every cell of ``BENCHMARK.json`` that
    serves sparse experts: the shapes the served combine runs at."""
    return {name: dict(k=m.num_experts_per_tok, d=m.hidden_size,
                       experts=m.num_experts, held=m.experts_held)
            for name, m in _serve_configs().items() if m.num_experts}


def _combine_operands(c, t, seed=0):
    """One layer's routing of ``t`` tokens as ``moe_mlp_nodrop`` makes it on
    the TPU (``k`` distinct experts a token drawn evenly over the router's
    width, a row whose expert is not held sorted behind the last group), and
    what the combine reads: ``(ys [tiles, d] bfloat16 in the kernel's tile
    layout, gate_w [t, k] float32, has_expert [t*k], here [t*k], order,
    sorted_tok, dest, group_sizes)``."""
    from deepspeedsyclsupport_tpu.ops import grouped_gemm as gg

    k, e, held = c["k"], c["experts"], c["held"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    scores = jax.random.uniform(ks[0], (t, e))
    gate_w, idx = jax.lax.top_k(scores, k)
    here = idx.reshape(t * k)
    has_expert = here < held
    here = jnp.where(has_expert, here, held)
    order = jnp.argsort(here, stable=True)
    group_sizes = jnp.bincount(here, length=held + 1)[:held].astype(jnp.int32)
    tiles = gg.tile_rows(group_sizes, here[order], gg.row_tile(t * k, e))
    ys = jax.random.normal(ks[1], (tiles.src.shape[0], c["d"]), jnp.bfloat16)
    return (ys, gate_w, has_expert, here.astype(jnp.int32), order,
            jnp.repeat(jnp.arange(t), k)[order], tiles.dest, group_sizes)


def _combine_scatter_add(ys, gate_w, has_expert, here, order, sorted_tok,
                         dest, group_sizes):
    """The combine of PRs 26-57 as it stood: the tiles gathered to sorted
    order, each row weighted in the rows' dtype, one scatter-add."""
    t = gate_w.shape[0]
    ys = ys[dest] * gate_w.reshape(-1)[order].astype(ys.dtype)[:, None]
    ys = jnp.where(has_expert[order][:, None], ys, 0)
    return jnp.zeros((t, ys.shape[1]), ys.dtype).at[sorted_tok].add(ys)


def _inv_argsort(here, order, group_sizes):
    return jnp.argsort(order)


def _inv_scatter(here, order, group_sizes):
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)


def _inv_rank(here, order, group_sizes):
    """A row's place in the sort from the groups' running sum and its rank
    among the rows of its own group (a cumulative one-hot; the rows in no
    group are the last one's)."""
    g = group_sizes.shape[0]
    hot = jax.nn.one_hot(here, g + 1, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(hot, axis=0) - hot,
                               here[:, None], axis=1)[:, 0]
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(group_sizes)])
    return start[here] + rank


# the candidates for the sort's inverse, each timed ALONE;
# ``moe_mlp_nodrop`` takes the first
COMBINE_INV = {"argsort": _inv_argsort, "scatter": _inv_scatter,
               "rank": _inv_rank}


def _combine_tree(moe):
    """``moe``'s combine as ``moe_mlp_nodrop`` calls it on the kernel's
    path."""
    def form(ys, gate_w, has_expert, here, order, sorted_tok, dest,
             group_sizes):
        return moe.combine_rows(ys, dest[jnp.argsort(order)], gate_w,
                                has_expert, ys.dtype)
    return form


def combine(argv=()):
    """The experts' combine ALONE at the served sparse cells' ``(k, d,
    experts held)`` and ``--rows`` tokens a forward, its device time read
    off a profiler trace, one layer a program: ``scatter_add`` (the form of
    PRs 26-57, written out above), ``tree`` (``parallel/moe.combine_rows``
    behind the inverse ``moe_mlp_nodrop`` takes), ``--parent DIR``'s
    ``combine_rows`` where it has one, and each candidate for the sort's
    inverse alone (``inv_<name>``).
    Each row: us a layer, and the GB/s of what a combine has to move (every
    (token, choice) row read once, every token's written once). ``err``: the
    largest error of ``scatter_add`` and of ``tree`` against a float64 sum
    on the host."""
    import argparse
    import importlib.util

    from deepspeedsyclsupport_tpu.parallel import moe as tree

    cells = _combine_cells()
    ap = argparse.ArgumentParser(prog="tpu_tune.py combine")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--cell", nargs="*", default=list(cells))
    ap.add_argument("--rows", type=int, nargs="*", default=list(COMBINE_ROWS))
    a = ap.parse_args(list(argv))
    parent = None
    if a.parent:
        # a module of the package, so that its relative imports resolve
        spec = importlib.util.spec_from_file_location(
            "deepspeedsyclsupport_tpu.parallel.moe_parent", os.path.join(
                a.parent, "deepspeedsyclsupport_tpu", "parallel", "moe.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    for cell in a.cell:
        c = cells[cell]
        for t in a.rows:
            args = _combine_operands(c, t)
            plan = {"scatter_add": _combine_scatter_add,
                    "tree": _combine_tree(tree)}
            if parent is not None and hasattr(parent, "combine_rows"):
                plan["parent"] = _combine_tree(parent)
            for n, f in COMBINE_INV.items():
                plan[f"inv_{n}"] = lambda *ar, f=f: f(ar[3], ar[4], ar[7])
            steps = {name: _named(name, fn, tag).lower(*args).compile()
                     for tag, (name, fn) in enumerate(plan.items())}
            rows = _traced_kernels(steps, args,
                                   kernel_of=lambda text: "kernel")
            moved = (c["k"] + 1) * t * c["d"] * 2
            for row in rows.values():
                row.pop("calls", None)
                row["us"] = round(1e3 * row.pop("xla"), 1)
                if row["us"]:
                    row["gb_s"] = round(moved / row["us"] / 1e3, 1)
            want = _combine_float64(args)
            err = {name: float(np.max(np.abs(np.asarray(
                steps[name](*args)[0], np.float64) - want)))
                for name in ("scatter_add", "tree")}
            # every candidate's inverse is the same permutation
            inv = [np.asarray(steps[f"inv_{n}"](*args)[0])
                   for n in COMBINE_INV]
            temp = steps["tree"].memory_analysis().temp_size_in_bytes
            emit("combine", cell=cell, t=t, **c, moved_bytes=moved,
                 rows=rows, err=err, tree_temp_mib=round(temp / 2**20, 1),
                 inv_differ=int(sum((i != inv[0]).sum() for i in inv[1:])))


# ---------------------------------------------------------------- bsa
BSA_CELL = dict(heads=32, kv_heads=2, dim=128, page=64, rows=8, atom=128,
                sparse_layers=3, la_heads=32, la_layers=9, la_chunk=128)
BSA_CTX = (32768, 65536, 98304)


def _bsa_operands(ctx, seed=0):
    """One sequence slot of ``ctx`` cached tokens a row (8 slots, pages dealt
    in no order), its keys, values and pooled keys in pools of one layer,
    and queries for an atom of 128 rows that ends at ``ctx`` and for 8
    one-token rows."""
    from deepspeedsyclsupport_tpu.ops import sparse_block as sb

    c = BSA_CELL
    sizes = sb.Sizes(64, 32, 16, 1, 32, 96, 8192)
    bps, s = ctx // c["page"], c["rows"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    blocks = s * bps
    pool = lambda k: jax.random.normal(                     # noqa: E731
        k, (1, blocks * c["page"], c["kv_heads"], c["dim"]), jnp.bfloat16)
    k_pool, v_pool = pool(keys[0]), pool(keys[1])
    tables = jax.random.permutation(keys[2], blocks).reshape(s, bps).astype(
        jnp.int32)
    ck = jnp.zeros((1, blocks, 4, c["kv_heads"], c["dim"]), jnp.bfloat16)
    q_atom = jax.random.normal(keys[3], (1, c["atom"], c["heads"], c["dim"]),
                               jnp.bfloat16)
    q_rows = jax.random.normal(keys[4], (s, c["heads"], c["dim"]),
                               jnp.bfloat16)
    return sizes, k_pool, v_pool, ck, tables, q_atom, q_rows


def bsa(argv=()):
    """See the module's docstring."""
    import argparse

    from deepspeedsyclsupport_tpu.ops import paged_attention as pa
    from deepspeedsyclsupport_tpu.ops import sparse_block as sb
    from deepspeedsyclsupport_tpu.ops import ssm

    ap = argparse.ArgumentParser(prog="tpu_tune.py bsa")
    ap.add_argument("--ctx", type=int, nargs="*", default=list(BSA_CTX))
    ap.add_argument("--parent", default=None)
    ap.add_argument("--parity", action="store_true")
    a = ap.parse_args(list(argv))
    c = BSA_CELL
    parent = _load_paged(a.parent)[1] if a.parent else None
    for ctx in a.ctx:
        sizes, k_pool, v_pool, ck, tables, q_atom, q_rows = _bsa_operands(ctx)
        s, bq, kvh = c["rows"], c["atom"], c["kv_heads"]
        # the pooled keys of every sequence, written a 768-row stretch a call
        def write(ck, at):
            pos = at + jnp.arange(768)
            for seq in range(s):
                ck = sb.pool_write(ck, k_pool, 0, tables,
                                   jnp.full((768,), seq), pos,
                                   jnp.ones((768,), bool), sizes)
            return ck
        fill = jax.jit(write, donate_argnums=0)
        for at in range(0, ctx, 768):
            ck = fill(ck, at)
        c_seq = jax.jit(lambda ck: sb.seq_pooled_keys(ck, 0, tables))(ck)
        pos_atom = (ctx - bq + jnp.arange(bq))[None]
        pos_rows = jnp.full((s,), ctx - 1)
        one = jnp.ones((1,), jnp.int32)

        def named(name, fn):
            fn.__name__ = name
            return jax.jit(fn)

        def atom_select(impl):
            def f(q):
                sc = sb.block_scores(q, c_seq, jnp.zeros((1,), jnp.int32),
                                     pos_atom, one * bq, sizes)
                return sb.select_blocks(sc, pos_atom, one * bq, sizes, impl)
            return f

        def rows_select(impl):
            def f(q):
                sc = sb.block_scores(q[:, None], c_seq, jnp.arange(s),
                                     pos_rows[:, None],
                                     jnp.ones((s,), jnp.int32), sizes)
                sel = sb.select_blocks(sc[:, 0][None], pos_rows[None],
                                       one * s, sizes, impl)[0]
                return sb.page_tables(sel, tables, pos_rows, sizes)
            return f

        sel_atom = jax.jit(atom_select("pallas"))(q_atom)
        row_tables, row_lens = jax.jit(rows_select("pallas"))(q_rows)
        if a.parity:
            want = jax.jit(atom_select("xla"))(q_atom)
            t_x, l_x = jax.jit(rows_select("xla"))(q_rows)
            emit("bsa_parity", ctx=ctx,
                 atom_selection_differs=int(jnp.sum(sel_atom != want)),
                 rows_tables_differ=int(jnp.sum(row_tables != t_x)),
                 rows_lens_differ=int(jnp.sum(row_lens != l_x)),
                 blocks_a_row=int(sel_atom[0, -1, 0].sum()))
        tiles = lambda t: jnp.repeat(t, kvh, axis=0)           # noqa: E731

        def atom_attend(impl, mod=pa, keys=False):
            """The atom under its selection a KV head as ``bsa.attend_atoms``
            hands it over: the blocks; ``keys``: widened to keys first, the
            form PRs 59-67 took."""
            def f(q):
                sel = jnp.repeat(jnp.swapaxes(sel_atom, 1, 2), sizes.block,
                                 axis=-1) if keys \
                    else jnp.transpose(sel_atom, (0, 2, 3, 1))
                return mod.ragged_prefill_attention(
                    q, k_pool, v_pool, tables[:1], one * (ctx - bq), one * bq,
                    block_size=sizes.block, layer=0, impl=impl, sel=sel,
                    name="bsa_prefill")
            return f

        def rows_attend(impl):
            return lambda q: pa.paged_decode_attention(
                tiles(q), k_pool, v_pool, row_tables, row_lens,
                block_size=sizes.block, impl=impl, layer=0, name="bsa_rows")

        def rows_dense(q):
            return pa.paged_decode_attention(
                q, k_pool, v_pool, tables, pos_rows + 1,
                block_size=sizes.block, impl="pallas", layer=0)

        if a.parity:
            got = jax.jit(rows_attend("pallas"))(q_rows).astype(jnp.float32)
            ref = jax.jit(rows_attend("xla"))(q_rows).astype(jnp.float32)
            emit("bsa_parity_rows", ctx=ctx, max_abs=float(
                jnp.max(jnp.abs(got - ref))), scale=float(jnp.std(ref)))
            got = jax.jit(atom_attend("pallas"))(q_atom).astype(jnp.float32)
            ref = jax.jit(atom_attend("xla"))(q_atom).astype(jnp.float32)
            was = {} if parent is None else dict(differs_from_parent=int(
                jnp.sum(got != jax.jit(atom_attend("pallas", parent, True))(
                    q_atom).astype(jnp.float32))))
            emit("bsa_parity_atom", ctx=ctx, max_abs=float(
                jnp.max(jnp.abs(got - ref))), scale=float(jnp.std(ref)),
                 **was)
        steps = {
            "scores_select_atom": named("scores_select_atom",
                                        atom_select("pallas")),
            "scores_select_rows": named("scores_select_rows",
                                        rows_select("pallas")),
            "attend_atom_blocks": named("attend_atom_blocks",
                                        atom_attend("pallas")),
            **({} if parent is None else {
                "attend_atom_keys_parent": named(
                    "attend_atom_keys_parent",
                    atom_attend("pallas", parent, True))}),
            "attend_rows_pages": named("attend_rows_pages",
                                       rows_attend("pallas")),
            "attend_rows_dense": named("attend_rows_dense", rows_dense)}
        out = {}
        for name, f in steps.items():
            q = q_atom if "atom" in name else q_rows
            res = _traced_kernels({name: f.lower(q).compile()}, (q,),
                                  kernel_of=lambda text: "kernel")
            out[name] = res[name]
        # what a one-token row reads: its pages' K and V, a KV head's tile
        # reading both heads' halves of a page (the pool is token-major)
        read = s * kvh * int(row_lens[0] // sizes.block + 1) * sizes.block \
            * kvh * c["dim"] * 2 * 2
        emit("bsa", ctx=ctx, ms=out, rows_page_bytes=read,
             dense_row_bytes=s * ctx * kvh * c["dim"] * 2 * 2)
    # lightning: the state step of 8 rows and six pieces of 128, one layer
    h, d, rows = c["la_heads"], c["dim"], c["rows"]
    pool = jax.jit(lambda key: 0.1 * jax.random.normal(
        key, (1, rows + 1, h, d, d)))(jax.random.PRNGKey(3))
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (768, h, d))
               for i in range(3))

    def step(pool, q, k, v):
        y, pool = ssm.lightning_step(
            q[:rows], k[:rows], v[:rows], pool, 0, jnp.arange(rows),
            jnp.zeros((rows,), bool), ssm.STATE_STEPS["pallas"])
        return pool, y

    def pieces(pool, q, k, v):
        n = 768 // c["la_chunk"]
        y, pool = ssm.lightning_pieces(
            q, k, v, pool, 0,
            (jnp.arange(n) * c["la_chunk"], jnp.full((n,), c["la_chunk"]),
             jnp.zeros((n,), jnp.int32), jnp.arange(n) == 0, jnp.asarray(n)),
            c["la_chunk"], jnp.bfloat16)
        return pool, y

    out = {}
    for name, fn in (("la_step", step), ("la_pieces", pieces)):
        fn.__name__ = name
        prog = jax.jit(fn, donate_argnums=0).lower(pool, q, k, v).compile()
        res = _traced_kernels({name: prog}, (q, k, v),
                              kernel_of=lambda text: "kernel", carry=pool)
        out[name] = res[name]
        pool = jax.jit(lambda key: 0.1 * jax.random.normal(
            key, (1, rows + 1, h, d, d)))(jax.random.PRNGKey(3))
    moved = 2 * rows * h * d * d * 4
    kernel = out["la_step"].get("kernel")
    emit("bsa_lightning", ms_a_layer=out, step_bytes=moved,
         step_peak_pct=round(100 * moved / V5E_HBM / (kernel * 1e-3), 1)
         if kernel else None)


# ---------------------------------------------------------------- proj
PROJ_ROWS = (16, 32, 48, 256, 768)
# how a product reads its weight, by the layout the stack is stored in: a
# projection ``[T, in] x W``; latent attention's two batched products over
# the parts of ``w_kvb``, ``in_out`` the public leaf WHOLE ``[r, h x (nope +
# v)]`` (reshaped by head and cut inside the program, as a forward handed the
# public tree does), ``out_in`` the part alone, head-major
PROJ_LAYOUTS = {"in_out": "td,dq->tq", "out_in": "td,qd->tq"}
PROJ_PARTS = {"uk": {"in_out": "thn,rhn->thr", "out_in": "thn,hrn->thr"},
              "uv": {"in_out": "thr,rhv->thv", "out_in": "thr,hvr->thv"}}


def _proj_cells():
    """``{cell: {product: shape}}`` of every serving cell. A projection's
    shape is ``(in, out)``: ``q`` / ``kv`` (:func:`model._qkv`), ``la`` (a
    lightning layer's three), ``qb`` (latent attention's queries from their
    latent), ``qi`` (an indexer's queries). A batched product's is ``(heads,
    in, out, the other part's width)``: ``uk`` (the queries into the latent,
    contracted over ``nope``), ``uv`` (the attended latents up to values)."""
    cells = {}
    for name, m in _serve_configs().items():
        if m.kv_lora_rank:
            h, r = m.num_heads, m.kv_lora_rank
            n, v = m.qk_nope_head_dim, m.v_head_dim
            cells[name] = {
                "qb": (m.q_lora_rank, h * (n + m.qk_rope_head_dim)),
                "uk": (h, n, r, v), "uv": (h, r, v, n)}
        else:
            cells[name] = {"q": (m.hidden_size, m.q_dim),
                           "kv": (m.hidden_size, m.kv_dim)}
        if m.lightning_heads:
            cells[name]["la"] = (m.hidden_size,
                                 m.lightning_heads * m.lightning_head_dim)
        if m.index_topk:
            cells[name]["qi"] = (
                m.q_lora_rank if m.index_q_latent else m.hidden_size,
                m.index_heads * m.index_head_dim)
    return cells


def _proj_operands(which, shape, layers, key):
    """One product at ``shape``: ``(x's shape behind the rows, {layout: the
    stack of weights}, {layout: product(x, a layer's weight)})``."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M

    def drawn(*dims):
        return (jax.random.normal(key, (layers, *dims), jnp.float32)
                * dims[0] ** -0.5).astype(jnp.bfloat16)

    if which not in PROJ_PARTS:
        stack = drawn(*shape)
        return ((shape[0],),
                {"in_out": stack, "out_in": jnp.swapaxes(stack, 1, 2)},
                {layout: functools.partial(jnp.einsum, form)
                 for layout, form in PROJ_LAYOUTS.items()})
    heads, d_in = shape[:2]
    nope, r, v = shape[1:] if which == "uk" else (shape[3], *shape[1:3])
    whole = drawn(r, heads * (nope + v))
    part = PROJ_PARTS[which]
    cut = slice(None, nope) if which == "uk" else slice(nope, None)
    f32 = dict(preferred_element_type=jnp.float32) if which == "uk" else {}
    return ((heads, d_in),
            {"in_out": whole,
             "out_in": M._split(heads, nope)(whole)[which == "uv"]},
            {"in_out": lambda x, w: jnp.einsum(
                part["in_out"], x, w.reshape(r, heads, -1)[..., cut], **f32),
             "out_in": lambda x, w: jnp.einsum(part["out_in"], x, w, **f32)})


def _proj_program(product, passes):
    """``passes`` walks of a stack of weights, each a scan whose xs are the
    stack (the layer loop of the serving forwards; the outer loop a looped
    model's): ``product(the rows, the layer's weight)`` summed over the
    layers."""
    def walk(x, stack):
        def layer(acc, w):
            return acc + product(x, w).astype(jnp.float32), None

        def one_pass(acc, _):
            return jax.lax.scan(layer, acc, stack)[0], None

        acc = jnp.zeros(jax.eval_shape(product, x, stack[0]).shape,
                        jnp.float32)
        return jax.lax.scan(one_pass, acc, None, length=passes)[0]
    return walk


def _largest_copy(text):
    """``(MiB, shape as the text writes it)`` of the largest ``copy`` of a
    compiled program's text, ``(0, None)`` where it has none."""
    import re

    best = (0.0, None)
    for m in re.finditer(r"= (bf16|f32)\[([\d,]+)\]\S* copy\(", text):
        dims = [int(d) for d in m.group(2).split(",")]
        mib = np.prod(dims) * (2 if m.group(1) == "bf16" else 4) / 2**20
        if mib > best[0]:
            best = (round(float(mib), 1), f"{m.group(1)}[{m.group(2)}]")
    return best


def proj(argv=()):
    """A projection alone (or one of latent attention's batched products),
    both stored layouts, one program a (shape, rows, layout): see the
    module's text. Each row: us a layer (the program's device time off a
    profiler trace over layers x passes), the GB/s of the weights it reads,
    the program's temporaries and its largest copy."""
    import argparse

    cells = _proj_cells()
    ap = argparse.ArgumentParser(prog="tpu_tune.py proj")
    ap.add_argument("--cell", nargs="*", default=list(cells))
    ap.add_argument("--rows", type=int, nargs="*", default=list(PROJ_ROWS))
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--passes", type=int, default=1)
    a = ap.parse_args(list(argv))
    shapes = {}     # (a batched product's name, its shape) -> the cells' there
    for cell in a.cell:
        for which, shape in cells[cell].items():
            shapes.setdefault((which if which in PROJ_PARTS else "", shape),
                              []).append(f"{cell}:{which}")
    for (which, shape), users in shapes.items():
        key = jax.random.PRNGKey(sum(shape))
        behind, stacks, products = _proj_operands(which, shape, a.layers, key)
        jax.block_until_ready(stacks)
        weight = 2 * int(np.prod(shape[:3] if which else shape))
        for t in a.rows:
            x = jax.random.normal(jax.random.fold_in(key, t), (t, *behind),
                                  jnp.bfloat16)
            rows = {}
            for tag, layout in enumerate(PROJ_LAYOUTS):
                args = (x, stacks[layout])
                step = _named(layout, _proj_program(products[layout],
                                                    a.passes),
                              tag).lower(*args).compile()
                got = _traced_kernels({layout: step}, args,
                                      kernel_of=lambda text: "kernel")
                us = 1e3 * got[layout]["xla"] / (a.layers * a.passes)
                mib, copied = _largest_copy(step.as_text())
                rows[layout] = {
                    "us": round(us, 2),
                    "gb_s": round(weight / us / 1e3, 1) if us else None,
                    "temp_mib": round(step.memory_analysis(
                    ).temp_size_in_bytes / 2**20, 1),
                    "copy_mib": mib, "copy": copied,
                    "sum": float(jnp.sum(step(*args)[0]))}
            d_in, d_out = shape[1:3] if which else shape
            emit("proj", d_in=d_in, d_out=d_out, t=t, layers=a.layers,
                 passes=a.passes, cells=users, rows=rows,
                 **({"part": which, "heads": shape[0]} if which else {}))


def _combine_float64(args):
    """The combine's sum on the host in float64, token by token."""
    ys, gate_w, has_expert, _here, order, sorted_tok, dest, _sizes = (
        np.asarray(a) for a in args)
    ys = ys.astype(np.float64)
    out = np.zeros((gate_w.shape[0], ys.shape[1]))
    w = gate_w.reshape(-1).astype(np.float64)
    for i, (row, tok) in enumerate(zip(order, sorted_tok)):
        if has_expert[row]:
            out[tok] += w[row] * ys[dest[i]]
    return out


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("calib", "all"):
        calib()
    if which in ("flash", "all"):
        flash(sys.argv[2:] if which == "flash" else ())
    if which in ("paged", "all"):
        paged(sys.argv[2:] if which == "paged" else ())
    if which in ("retention", "all"):
        retention(sys.argv[2:] if which == "retention" else ())
    if which in ("dsa", "all"):
        dsa(sys.argv[2:] if which == "dsa" else ())
    if which in ("kda", "all"):
        kda(sys.argv[2:] if which == "kda" else ())
    if which in ("conv", "all"):
        conv(sys.argv[2:] if which == "conv" else ())
    if which in ("combine", "all"):
        combine(sys.argv[2:] if which == "combine" else ())
    if which in ("bsa", "all"):
        bsa(sys.argv[2:] if which == "bsa" else ())
    if which in ("proj", "all"):
        proj(sys.argv[2:] if which == "proj" else ())
