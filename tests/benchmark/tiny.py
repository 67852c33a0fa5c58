"""A tiny benchmark in a temporary directory: the same harness functions at
CPU size, and the proof that a configuration (one of a model family the
benchmark has never seen among them), a traffic mix, a per-layer metric and
a cell are each added by new files and new ``BENCHMARK.json`` entries alone
(nothing under ``benchmark/`` is edited or copied here)."""
import json
import types
from pathlib import Path

TINY_MISTRAL = {
    "source": "tests", "model_type": "mistral", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "sliding_window": None, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "preset": "tiny", "overrides": {"attn_impl": "flash"},
    "dtype": "bfloat16"}
TINY_PHI = {
    "source": "tests", "model_type": "phi", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "partial_rotary_factor": 0.4, "rope_theta": 10000.0,
    "layer_norm_eps": 1e-05, "path": "serve", "preset": "phi-2",
    "overrides": {"hidden_size": 64, "intermediate_size": 128,
                  "num_layers": 2, "num_heads": 4, "num_kv_heads": 4,
                  "head_dim": 16, "vocab_size": 512},
    "dtype": "float32",
    "engine": {"max_context": 128, "max_sequences": 4, "num_blocks": 32,
               "block_size": 16, "max_tokens_per_batch": 32,
               "prefill_attn": "xla", "decode_attn": "xla"},
    "policy": {"admission": "none"}}
# a THIRD family, sparse experts, which ``benchmark/families`` does not
# have: its configuration below and its module (``NEW_FAMILY``) are files
# the temporary directory adds
TINY_MIXTRAL = {
    "source": "tests", "model_type": "mixtral", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "sliding_window": None, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "path": "serve", "preset": "tiny-moe", "overrides": {},
    "dtype": "float32", "engine": TINY_PHI["engine"],
    "policy": {"admission": "none"}}
NEW_FAMILY = '''"""``model_type: mixtral`` — the mistral block with the MLP replaced by
sparse experts: a softmax router over all experts, the top
``num_experts_per_tok`` renormalised, each a SwiGLU (HF ``modeling_mixtral``).
Plain: every expert runs on every token and the gates zero the rest."""
import jax
import jax.numpy as jnp

from benchmark import flops
from benchmark import reference as ref


def arch(hf):
    head_dim = hf["hidden_size"] // hf["num_attention_heads"]
    return {"hidden_size": hf["hidden_size"],
            "intermediate_size": hf["intermediate_size"],
            "num_layers": hf["num_hidden_layers"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim": head_dim, "rotary_dim": head_dim,
            "vocab_size": hf["vocab_size"], "rope_theta": hf["rope_theta"],
            "sliding_window": hf.get("sliding_window"),
            "norm_eps": hf["rms_norm_eps"],
            "num_experts": hf["num_local_experts"],
            "num_experts_per_tok": hf["num_experts_per_tok"]}


def program_widths(hf):
    a = arch(hf)
    return {k: a[k] for k in (
        "hidden_size", "intermediate_size", "num_layers", "num_heads",
        "num_kv_heads", "head_dim", "vocab_size", "num_experts",
        "num_experts_per_tok")}


def experts(a, p, x):
    probs = jax.nn.softmax(x @ p["router"], axis=-1)            # [S, E]
    top_w, top_i = jax.lax.top_k(probs, a["num_experts_per_tok"])
    top_w = top_w / top_w.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(top_i, a["num_experts"]) * top_w[..., None]).sum(1)
    g = jnp.einsum("sd,edf->esf", x, p["w_gate"])
    h = g / (1.0 + jnp.exp(-g)) * jnp.einsum("sd,edf->esf", x, p["w_up"])
    return jnp.einsum("se,esd->sd", gates,
                      jnp.einsum("esf,efd->esd", h, p["w_down"]))


def sequence_logits(a, params, ids):
    norm = lambda p, x: ref.rms_norm(p, x, a["norm_eps"])  # noqa: E731

    def block(p, x):
        x = x + ref.attention(a, p["attn"], norm(p["attn_norm"], x))
        return x + experts(a, p["moe"], norm(p["mlp_norm"], x))

    return ref.decoder_logits(params, ids, block, norm)


def matmul_params(a):
    """Weights a token meets: its own experts only, and the router."""
    d = a["hidden_size"]
    mlp = a["num_experts_per_tok"] * 3 * d * a["intermediate_size"] \\
        + d * a["num_experts"]
    return a["num_layers"] * (flops.attention_params(a) + mlp) \\
        + d * a["vocab_size"]


def train_flops_per_token(a, seq):
    return 6 * matmul_params(a) + flops.attention_train_flops(a, seq)
'''
# a pool of 8 blocks where 4 live sequences can want 20: a stalled host's
# backlog runs it out, under each of the program's two preemption policies
TIGHT = {policy: {**TINY_PHI,
                  "engine": {**TINY_PHI["engine"], "num_blocks": 8},
                  "policy": {"admission": "none", "preempt_policy": policy}}
         for policy in ("reject", "requeue")}
CONFIGS = {
    "tiny-serve": TINY_PHI,
    "tiny-tight-reject": TIGHT["reject"],
    "tiny-tight-requeue": TIGHT["requeue"],
    "tiny-moe-serve": TINY_MIXTRAL,
    "tiny-train": {**TINY_MISTRAL, "path": "train", "train": {
        "batch": 4, "zero_stage": 0, "fsdp": 1, "lr": 0.001}},
    "tiny-zero3": {**TINY_MISTRAL, "path": "zero3", "train": {
        "batch": 8, "zero_stage": 3, "fsdp": 4, "lr": 0.001}}}
TRAFFIC = {
    "tiny-closed": {"kind": "closed", "clients": 4, "count": 8,
                    "prompt_len": {"dist": "uniform", "min": 4, "max": 40},
                    "output_len": {"dist": "uniform", "min": 3, "max": 8}},
    "tiny-lanes": {"kind": "closed", "clients": 4, "count": 8,
                   "order": "lanes",
                   "prompt_len": {"dist": "uniform", "min": 4, "max": 40},
                   "output_len": {"dist": "uniform", "min": 3, "max": 8}},
    "tiny-open": {"kind": "open-fixed-rate", "rate_per_s": 8.0,
                  "jitter_gaps": 0.5, "ramp_seconds": 0.5,
                  "prompt_len": {"dist": "lognormal", "median": 20,
                                 "sigma": 0.8, "min": 4, "max": 60},
                  "output_len": {"dist": "uniform", "min": 2, "max": 6}},
    "tiny-open-fast": {"kind": "open-fixed-rate", "rate_per_s": 40.0,
                       "jitter_gaps": 0.5, "ramp_seconds": 0.5,
                       "prompt_len": {"dist": "lognormal", "median": 20,
                                      "sigma": 0.8, "min": 4, "max": 60},
                       "output_len": {"dist": "uniform", "min": 10,
                                      "max": 24}},
    "tiny-batches": {"kind": "train-batches", "seq_len": 128,
                     "warm_steps": 2}}
CELLS = [("tiny-closed-cell", "tiny-serve", "tiny-closed", 1),
         ("tiny-moe-cell", "tiny-moe-serve", "tiny-closed", 1),
         ("tiny-open-cell", "tiny-serve", "tiny-open", 1),
         ("tiny-reject-cell", "tiny-tight-reject", "tiny-open-fast", 1),
         ("tiny-requeue-cell", "tiny-tight-requeue", "tiny-open-fast", 1),
         ("tiny-train-cell", "tiny-train", "tiny-batches", 1),
         ("tiny-zero3-cell", "tiny-zero3", "tiny-batches", 4)]
NEW_METRIC = '''"""A metric a later PR adds: rounds per second of window."""


def read(obs):
    t0, t1 = obs["window"]
    return sum(1 for r in obs["rounds"] if t0 < r[1] <= t1) / (t1 - t0)
'''


def make_root(tmp):
    """``tmp/BENCHMARK.json`` plus
    ``tmp/extra/{configs,traffic,metrics,families}``: the real document with
    tiny cells, configurations, mixes, one new metric and one new model
    family ADDED; the readers and families that are there are found under
    the real ``benchmark/`` path, which the document keeps naming."""
    from benchmark import spec

    tmp = Path(tmp)
    doc = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(spec.ROOT / "benchmark"), "extra"]
    for c in doc["configs"]:
        c["file"] = str(spec.ROOT / c["file"])
    for sub in ("configs", "traffic", "metrics", "families"):
        (tmp / "extra" / sub).mkdir(parents=True)
    for name, cfg in CONFIGS.items():
        (tmp / "extra" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        doc["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "tiny",
            "file": f"extra/configs/{name}.json"})
    for name, mix in TRAFFIC.items():
        (tmp / "extra" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    (tmp / "extra" / "metrics" / "rounds_per_s.py").write_text(NEW_METRIC)
    (tmp / "extra" / "families" / "mixtral.py").write_text(NEW_FAMILY)
    doc["per_layer"].append({
        "name": "rounds_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "serve engine",
        "moves": "ttft_p90_ms", "workloads": ["tiny-open-cell"]})
    for name, config, mix, chips in CELLS:
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": mix, "chips": chips,
                                 "why": "tiny"})
        path = CONFIGS[config]["path"]
        for m in doc["end_to_end"] + doc["per_layer"]:
            like = {"serve": "phi2-decode-sat" if "closed" in mix
                    else "phi2-prefill-mix",
                    "train": "mistral7b-train-1chip",
                    "zero3": "mistral7b-zero3-4chip"}[path]
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.Bench(tmp)


class NoHooks:
    """The window's edges with no profiler (the chip's part)."""
    trace_s = tail_s = 0.0

    def __init__(self):
        import time

        self.clock = time.perf_counter
        self.t_open = self.t_close = None
        self.compiles = []

    def window_open(self):
        self.t_open = self.clock()

    def window_close(self):
        self.t_close = self.clock()

    def tick(self):
        pass


def drive(bench, cell_name, seed=0, seconds=1.0, traffic=None):
    """What ``benchmark.run.main`` does after its device check, on the CPU:
    returns the observations and every metric the cell's readers give.
    ``traffic`` names another mix than the cell's own."""
    from benchmark import run

    cell = bench.cell(cell_name)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(traffic or cell["traffic"])
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    obs = {"cell": cell, "config": cfg, "traffic": mix, "chips": cell["chips"],
           "family": bench.family(cfg),
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "seconds": seconds, "trace": None, "setup_s": 1.0}
    run.PATHS[cfg["path"]](cfg, mix, args, NoHooks(), {}, obs)
    metrics = {}
    for section in ("end_to_end", "per_layer"):
        for m in bench.metrics_of(cell_name, section):
            value = bench.reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = value
    return obs, metrics
