"""Finding the benchmark's data by name, and checking it.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them sits in a file of its own under one of the
benchmark's ``paths``:

* a configuration — the ``file`` its ``configs`` entry names;
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
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_KINDS = ("closed", "open-fixed-rate", "train-batches")
CONFIG_PATHS = ("train", "zero3", "serve")


def load_json(path):
    with open(path) as f:
        return json.load(f)


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

    def config(self, name):
        entry = self._entry("configs", name)
        return {**load_json(self.root / entry["file"]), "name": name}

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

    def reader(self, name):
        """``read(obs)`` of the per-layer metric ``name``."""
        f = self._find("metrics", name, (".py", ".json"))
        if f.suffix == ".json":
            alias = load_json(f)
            inner = self.reader(alias["reader"])
            args = alias.get("args", {})
            return lambda obs: inner(obs, **args)
        return self._module("metrics", name).read

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
        also meet: names, units, sources, files found by name, and that each
        per-layer metric's ``moves`` is reported by every cell reporting it."""
        d, out = self.doc, []
        cells = [w["name"] for w in d["workloads"]]
        e2e = {m["name"]: m for m in d["end_to_end"]}
        for section in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[section]]
            out += [f"{section}: bad name {n!r}" for n in names
                    if not NAME_RE.match(n)]
            out += [f"{section}: duplicate name {n!r}" for n in set(names)
                    if names.count(n) > 1]
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
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{m['name']}: moves unknown {m['moves']!r}")
                continue
            moved = e2e[m["moves"]].get("workloads", cells)
            out += [f"{m['name']}: cell {c!r} does not report "
                    f"{m['moves']!r}" for c in m.get("workloads", cells)
                    if c not in moved]
            try:
                self.reader(m["name"])
            except (FileNotFoundError, KeyError, AttributeError) as e:
                out.append(f"{m['name']}: no reader ({e})")
        for w in d["workloads"]:
            for key in ("config", "traffic"):
                if not NAME_RE.match(w[key]):
                    out.append(f"{w['name']}: bad {key} {w[key]!r}")
            try:
                cfg = self.config(w["config"])
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
