"""Finding the benchmark's data by name, and checking it.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them sits in a file of its own under one of the
benchmark's ``paths``:

* a configuration — the ``file`` its ``configs`` entry names; what its
  ``reduced`` may hold is ``reduced_problems``' to say;
* a traffic mix — ``<path>/traffic/<name>.json``;
* a per-layer metric — ``<path>/metrics/<name>.py`` (a module with
  ``read(obs)``) or ``<path>/metrics/<name>.json`` (``{"reader": <other
  metric>, "args": {...}}``: an existing reader under a new name);
* a model family's plain reference and FLOP count —
  ``<path>/families/<model_type>.py``, by the ``model_type`` a
  configuration's file publishes (``benchmark.reference`` says what the
  module gives).

Nothing here lists names: a later PR adds files and ``BENCHMARK.json``
entries and edits nothing.
"""
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
NAME_RULE = "at most 64 of A-Za-z0-9_.- and starts with neither . nor -"
# the most entries the driver's contract lets each list hold: a file outside
# them is refused before a single run
MOST = {"configs": 24, "workloads": 24, "end_to_end": 16, "per_layer": 128}
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_KINDS = ("closed", "open-fixed-rate", "train-batches")
CONFIG_PATHS = ("train", "zero3", "serve")

# ``reduced``: what a configuration may change from its source. Every entry
# says what its key COUNTS; a key that counts nothing is a width.
CUTS = ("layers", "experts", "heads", "vocabulary")
SHARES = CUTS[1:]               # this chip's part of a layer, not fewer layers
SHARED_BY = (2, 4, 8, 16, 32)   # chips that may share each layer
MIN_EXPERTS_HELD = 8            # the model-configs guide's floors for a share
MIN_VOCABULARY_PART = 8         # ... at least an eighth of the rows
MIN_LAYERS_AFTER_DENSE = 4
# the published keys the floors read (the catalog's names; all integers)
DEPTH_KEYS = ("num_hidden_layers", "num_layers")
LEADING_DENSE_KEYS = ("first_k_dense_replace", "num_dense_layers",
                      "n_dense_first_layers")
HEADS_RE = re.compile(r"(^|_)heads$")
# what the driver refuses under ``reduced`` whatever the entry says of it
WIDTH_RE = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*)_size$|_dim$|_rank$"
    r"|head_size|expan\w*_factor|experts_per_tok")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def reduced_problems(cfg):
    """What is wrong with a configuration's ``reduced``, as sentences that
    name the key and the reason (empty = sound).

    An entry is ``{key: {"published", "run", "counts", "why"}}``: the file
    runs ``cfg[key] == run != published``, and ``counts`` says what kind of
    cut that is. ``"layers"``: the depth and the counts that go with it (the
    leading dense layers, the multi-token-prediction blocks). ``"experts"``,
    ``"heads"``, ``"vocabulary"``: this chip's SHARE of a layer that
    ``layer_shared_by`` chips divide between them, so ``run x
    layer_shared_by == published``, all head counts go together, and the
    guide's floors hold. Anything else is a width, and a width is never
    cut. The label is the builder's statement (a reviewer holds it against
    the published config); the arithmetic and the floors are checked here."""
    reduced, out, kinds = cfg.get("reduced", {}), [], {}
    for key, cut in reduced.items():
        counts = cut.get("counts")
        if counts not in CUTS:
            out.append(f"reduced[{key!r}]: counts is {counts!r}, not one of "
                       f"{CUTS}: a width is never cut")
            continue
        if WIDTH_RE.search(key):
            out.append(f"reduced[{key!r}]: the key names a width, whatever "
                       f"it is said to count: a width is never cut")
        if not (cfg.get(key) == cut.get("run") != cut.get("published")):
            out.append(f"reduced[{key!r}]: the file runs {cfg.get(key)!r}, "
                       f"the entry says run {cut.get('run')!r}, published "
                       f"{cut.get('published')!r} (want file == run != "
                       f"published)")
        if not cut.get("why"):
            out.append(f"reduced[{key!r}]: no why")
        kinds.setdefault(counts, []).append(key)
    shares = [key for counts in SHARES for key in kinds.get(counts, ())]
    if not shares:
        return out
    by = cfg.get("layer_shared_by")
    if by not in SHARED_BY:
        return out + [
            f"reduced[{key!r}]: a share ({reduced[key]['counts']}) needs "
            f"\"layer_shared_by\": N beside deployment, N in {SHARED_BY}; "
            f"the file has {by!r}" for key in shares]
    for key in shares:
        run, published = reduced[key]["run"], reduced[key]["published"]
        if run * by != published:
            out.append(f"reduced[{key!r}]: {run} x layer_shared_by {by} is "
                       f"not the published {published}")
    for key in kinds.get("experts", ()):
        if reduced[key]["run"] < MIN_EXPERTS_HELD:
            out.append(f"reduced[{key!r}]: {reduced[key]['run']} experts "
                       f"held, the floor is {MIN_EXPERTS_HELD}")
    for key in kinds.get("vocabulary", ()):
        run, published = reduced[key]["run"], reduced[key]["published"]
        if run * MIN_VOCABULARY_PART < published:
            out.append(f"reduced[{key!r}]: {run} of {published} rows, the "
                       f"floor is 1/{MIN_VOCABULARY_PART} of the vocabulary")
    if "heads" in kinds:
        whole = sorted(k for k, v in cfg.items() if HEADS_RE.search(k)
                       and isinstance(v, int) and k not in kinds["heads"])
        if whole:
            out.append(f"reduced[{kinds['heads'][0]!r}]: query and key-value "
                       f"heads are cut together or not at all; {whole} "
                       f"stay whole")
    depth_key = next((k for k in DEPTH_KEYS if k in cfg), None)
    dense = sum(cfg[k] for k in LEADING_DENSE_KEYS
                if isinstance(cfg.get(k), int))
    if depth_key and cfg[depth_key] < dense + MIN_LAYERS_AFTER_DENSE:
        out.append(f"reduced: a share at {depth_key} {cfg[depth_key]}: the "
                   f"floor is the {dense} leading dense layers + "
                   f"{MIN_LAYERS_AFTER_DENSE}")
    return out


class Bench:
    """``BENCHMARK.json`` of the checkout at ``root`` and the files it names."""

    def __init__(self, root=ROOT):
        self.root = Path(root)
        self.doc = load_json(self.root / "BENCHMARK.json")

    # ------------------------------------------------------------- lookups
    def _entry(self, section, name):
        for e in self.doc[section]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {section} entry named {name!r}; have "
                       f"{[e['name'] for e in self.doc[section]]}")

    def cell(self, name):
        return self._entry("workloads", name)

    def _config_file(self, name):
        entry = self._entry("configs", name)
        return {**load_json(self.root / entry["file"]), "name": name}

    def config(self, name):
        """The configuration as its file has it; one whose ``reduced`` cuts
        what may not be cut is refused here, before anything runs it."""
        cfg = self._config_file(name)
        wrong = reduced_problems(cfg)
        if wrong:
            raise ValueError(f"configuration {name!r}: " + "; ".join(wrong))
        return cfg

    def _find(self, sub, name, suffixes):
        for p in self.doc["paths"]:
            for suffix in suffixes:
                f = self.root / p / sub / (name + suffix)
                if f.is_file():
                    return f
        raise FileNotFoundError(
            f"no {sub}/{name}{'|'.join(suffixes)} under {self.doc['paths']}")

    def traffic(self, name):
        return {**load_json(self._find("traffic", name, (".json",))),
                "name": name}

    def _module(self, sub, name):
        f = self._find(sub, name, (".py",))
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{sub}_" + re.sub(r"\W", "_", name), f)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def resolved(self, name):
        """``(the reader's .py, its args)`` that the per-layer metric
        ``name`` comes to through its aliases: what two entries may not
        share together with their ``moves``."""
        f, args = self._find("metrics", name, (".py", ".json")), {}
        while f.suffix == ".json":
            alias = load_json(f)
            args = {**alias.get("args", {}), **args}
            f = self._find("metrics", alias["reader"], (".py", ".json"))
        return f.stem, args

    def reader(self, name):
        """``read(obs)`` of the per-layer metric ``name``."""
        stem, args = self.resolved(name)
        read = self._module("metrics", stem).read
        return (lambda obs: read(obs, **args)) if args else read

    def family(self, cfg):
        """The module of the configuration's model family: its plain
        reference and its FLOP count (``benchmark.reference.FAMILY_API``)."""
        from .reference import FAMILY_API

        mod = self._module("families", cfg["model_type"])
        missing = [f for f in FAMILY_API
                   if not callable(getattr(mod, f, None))]
        if missing:
            raise AttributeError(
                f"families/{cfg['model_type']}.py lacks {missing}")
        return mod

    def metrics_of(self, cell_name, section):
        """The ``end_to_end`` or ``per_layer`` entries ``cell_name`` reports."""
        return [m for m in self.doc[section]
                if "workloads" not in m or cell_name in m["workloads"]]

    # ---------------------------------------------------------- validation
    def problems(self):
        """Everything wrong with the benchmark's data, as a list of
        sentences (empty = sound). Checks what a later PR's added files must
        also meet: the contract's limits (entries a list, names, four-chip
        cells), units, sources, files found by name, and that each
        per-layer metric's ``moves`` is reported by every cell reporting it,
        and what each configuration's ``reduced`` cuts."""
        d, out = self.doc, []
        cells = [w["name"] for w in d["workloads"]]
        e2e = {m["name"]: m for m in d["end_to_end"]}
        for section, most in MOST.items():
            names = [e["name"] for e in d[section]]
            if len(names) > most:
                out.append(f"{section}: {len(names)} entries, the contract's "
                           f"most is {most}")
            out += [f"{section}: bad name {n!r} (a name is {NAME_RULE})"
                    for n in names if not NAME_RE.match(n)]
            out += [f"{section}: duplicate name {n!r}" for n in set(names)
                    if names.count(n) > 1]
        four = [w["name"] for w in d["workloads"] if w["chips"] == 4]
        if len(four) > max(1, len(cells) // 4):
            out.append(f"workloads: {len(four)} cells of {len(cells)} ask "
                       f"for 4 chips {four}; at most a quarter, rounded "
                       f"down, may ({len(cells) // 4}), and one always may")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                out.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["source"] not in SOURCES:
                out.append(f"{m['name']}: unknown source {m['source']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better is {m['better']!r}")
            out += [f"{m['name']}: unknown cell {w!r}"
                    for w in m.get("workloads", ()) if w not in cells]
        if "setup_s" not in e2e:
            out.append("end_to_end: no setup_s")
        first = {}      # (reader, args, moves) -> the entry that has it
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{m['name']}: moves unknown {m['moves']!r}")
                continue
            moved = e2e[m["moves"]].get("workloads", cells)
            out += [f"{m['name']}: cell {c!r} does not report "
                    f"{m['moves']!r}" for c in m.get("workloads", cells)
                    if c not in moved]
            try:
                stem, args = self.resolved(m["name"])
                self.reader(m["name"])
            except (FileNotFoundError, KeyError, AttributeError) as e:
                out.append(f"{m['name']}: no reader ({e})")
                continue
            key = (stem, json.dumps(args, sort_keys=True), m["moves"])
            if first.setdefault(key, m["name"]) != m["name"]:
                out.append(f"{m['name']}: the reader ({stem} {args}) and the "
                           f"moves ({m['moves']}) of {first[key]!r}: one "
                           f"entry a reader and a moved metric, so append "
                           f"the cell to that entry's workloads")
        for c in d["configs"]:
            try:
                cfg = self._config_file(c["name"])
            except FileNotFoundError as e:
                out.append(f"{c['name']}: {e}")
                continue
            out += [f"{c['name']}: {p}" for p in reduced_problems(cfg)]
            keys = sorted(cfg.get("reduced", {}))
            if c["reduced"] != keys:
                out.append(f"{c['name']}: BENCHMARK.json lists reduced "
                           f"{c['reduced']}, the file's keys sorted are "
                           f"{keys}")
        for w in d["workloads"]:
            for key in ("config", "traffic"):
                if not NAME_RE.match(w[key]):
                    out.append(f"{w['name']}: bad {key} {w[key]!r} (a name "
                               f"is {NAME_RULE})")
            try:
                cfg = self._config_file(w["config"])
                mix = self.traffic(w["traffic"])
            except (KeyError, FileNotFoundError) as e:
                out.append(f"{w['name']}: {e}")
                continue
            if cfg.get("path") not in CONFIG_PATHS:
                out.append(f"{w['config']}: path {cfg.get('path')!r}")
            try:
                self.family(cfg)
            except (KeyError, FileNotFoundError, AttributeError) as e:
                out.append(f"{w['config']}: no model family ({e})")
            if mix.get("kind") not in TRAFFIC_KINDS:
                out.append(f"{w['traffic']}: kind {mix.get('kind')!r}")
            if not self.metrics_of(w["name"], "per_layer"):
                out.append(f"{w['name']}: no per-layer metric")
            if len(self.metrics_of(w["name"], "end_to_end")) < 2:
                out.append(f"{w['name']}: needs setup_s and one more")
        used = {w["config"] for w in d["workloads"]}
        out += [f"configs: {c['name']!r} is used by no cell"
                for c in d["configs"] if c["name"] not in used]
        return out
