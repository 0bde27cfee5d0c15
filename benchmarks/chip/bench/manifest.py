"""Load and check ``BENCHMARK.json``, and find each cell's own files.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by its name:

  configs/<config>.json     sizes and quantization of one model, and the
                            names of its family and reference
  configs/<reference>.py    the plain reference a configuration names
  families/<family>.py      what is particular to one model family:
      PROGRAM_KEYS                        the config's keys the program's
                                          ArchConfig takes over
      raw_weights(cfg, key) -> raw        every array the reference reads,
                                          drawn from one key (jittable)
      fill_program(tree, cfg, raw) -> tree
                                          raw in the program's tree, whose
                                          shapes come from lm.init
      adapters(raw) -> {name: {"a", "b"}} the trainable QA-LoRA pairs
      serve_pass(cfg, rows) -> (flops, bytes)
                                          one forward pass's required work;
                                          rows: [(cache length, rows
                                          consumed)] per slot
      train_step(cfg, batch, seq) -> (flops, bytes)
                                          one QA-LoRA step's required work
      linear_serve_pass(cfg, rows), linear_train_step(cfg, batch, seq)
                                          the same, of the ``linear`` scope
      TINY, TINY_CONTROL                  CPU stand-in sizes for the cell
                                          tests and the control test
  traffic/<traffic>.json    parameters of one traffic mix
  cells/<workload>.json     engine settings and correctness limits
  metrics/<metric>.py       the reader of one per-layer metric

so a later family is a new file plus a configuration that names it, and a
later cell, mix or metric is new files plus their manifest entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}

_TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
             "end_to_end", "per_layer"}
_CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
_WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
_E2E_KEYS = {"name", "unit", "better", "bound", "source"}
_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class ManifestError(ValueError):
    pass


def _need(ok: bool, msg: str):
    if not ok:
        raise ManifestError(msg)


def _name(v, what: str):
    _need(isinstance(v, str) and bool(NAME_RE.match(v)),
          f"{what}: {v!r} is not a name (1-64 of A-Z a-z 0-9 _ . -, not "
          f"starting with . or -)")


def _line(v, what: str):
    _need(isinstance(v, str) and 1 <= len(v) <= 200 and "\n" not in v
          and "\t" not in v, f"{what}: 1-200 characters on one line")


def _keys(entry, allowed, optional, what):
    got = set(entry)
    _need(allowed <= got <= allowed | optional,
          f"{what}: keys {sorted(got)}, expected {sorted(allowed)}"
          + (f" (+ {sorted(optional)})" if optional else ""))


def _metric(m, keys, sources, what, workloads):
    _keys(m, keys, {"workloads"}, what)
    _name(m["name"], f"{what} name")
    _need(isinstance(m["unit"], str) and bool(UNIT_RE.match(m["unit"])),
          f"{what} {m['name']}: unit {m['unit']!r} is not 1-16 of "
          f"A-Z a-z 0-9 _ / % . -")
    _need(m["better"] in ("lower", "higher"),
          f"{what} {m['name']}: better is lower or higher")
    _need(m["source"] in sources,
          f"{what} {m['name']}: source {m['source']!r} not in "
          f"{sorted(sources)}")
    for w in m.get("workloads", ()):
        _need(w in workloads, f"{what} {m['name']}: unknown cell {w!r}")


def validate(man: dict) -> dict:
    """Raise :class:`ManifestError` where ``man`` breaks the benchmark's
    format; return it unchanged otherwise."""
    _keys(man, _TOP_KEYS, set(), "BENCHMARK.json")
    _need(isinstance(man["run_seconds"], int)
          and 1 <= man["run_seconds"] <= 51, "run_seconds: 1..51")
    configs = {}
    for c in man["configs"]:
        _keys(c, _CONFIG_KEYS, set(), "config")
        _name(c["name"], "config name")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        _need(len(c["reduced"]) <= 16, "reduced: at most 16 keys")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")
        configs[c["name"]] = c
    workloads = {}
    for w in man["workloads"]:
        _keys(w, _WORKLOAD_KEYS, set(), "workload")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        _need(w["config"] in configs, f"{w['name']}: unknown config")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips is 1 or 4")
        _line(w["why"], f"workload {w['name']} why")
        workloads[w["name"]] = w
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    _need(len(names) == len(set(names)), "metric names repeat")
    _need(len(workloads) == len(man["workloads"]), "cell names repeat")
    _need(len(configs) == len(man["configs"]), "config names repeat")
    e2e = set()
    for m in man["end_to_end"]:
        _metric(m, _E2E_KEYS, E2E_SOURCES, "end_to_end", workloads)
        _need(isinstance(m["bound"], (int, float))
              and 0 < m["bound"] <= 0.25, f"{m['name']}: bound in (0, 0.25]")
        e2e.add(m["name"])
    _need("setup_s" in e2e, "end_to_end needs setup_s")
    for m in man["per_layer"]:
        _metric(m, _LAYER_KEYS, SOURCES, "per_layer", workloads)
        _line(m["layer"], f"{m['name']} layer")
        _need(m["moves"] in e2e, f"{m['name']}: moves an unknown metric")
    return man


def load() -> dict:
    with open(MANIFEST) as f:
        return validate(json.load(f))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(man: dict, workload: str) -> dict:
    """One cell with everything it is run with: its workload entry, the
    configuration, traffic and cell files, and the metrics it reports."""
    w = {x["name"]: x for x in man["workloads"]}.get(workload)
    _need(w is not None, f"no workload {workload!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    e2e = [m for m in man["end_to_end"] if _applies(m, workload)]
    layer = [m for m in man["per_layer"] if _applies(m, workload)]
    return {
        "workload": w,
        "config": read_json(os.path.join(REPO, conf["file"])),
        "traffic": read_json(os.path.join(CHIP_DIR, "traffic",
                                          w["traffic"] + ".json")),
        "cell": read_json(os.path.join(CHIP_DIR, "cells", workload + ".json")),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(path: str, name: str):
    """Import one file by path, once (metric readers, families and
    references are found by name, and some names carry dots, so they are
    not importable as modules)."""
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def metric_reader(name: str):
    return load_module(os.path.join(CHIP_DIR, "metrics", name + ".py"),
                       "metric_" + name.replace(".", "_").replace("-", "_"))


def family(config: dict):
    return load_module(os.path.join(CHIP_DIR, "families",
                                    config["family"] + ".py"),
                       "family_" + config["family"].replace("-", "_"))


def reference(config: dict):
    return load_module(os.path.join(CHIP_DIR, "configs",
                                    config["reference"] + ".py"),
                       "reference_" + config["reference"])
