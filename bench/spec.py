"""``BENCHMARK.json``: loading, validation, and finding a cell's files.

Everything the harness runs is found by name:

* a configuration ``<config>`` is ``bench/configs/<config>.json`` (its sizes,
  source and cuts) with ``bench/configs/<config>.py`` beside it (the
  builder of the timed path, its FLOP and byte counts, its plain
  reference);
* a traffic mix ``<traffic>`` is ``bench/traffic/<traffic>.json``, a file of
  parameters the configuration's builder reads;
* a cell's correctness limits are ``bench/limits/<cell>.json``;
* a metric ``<metric>`` is read by ``bench/metrics/<metric>.py``.

Adding a configuration, traffic mix, cell or metric adds files and
entries; no file of the harness changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = SOURCES_E2E + ("program_span", "program_counter")


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, breaks the benchmark's rules."""


def _line(text, what):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise SpecError(f"{what}: 1 to 200 characters on one line, no tab")


def _name(text, what):
    if not (isinstance(text, str) and NAME.match(text)):
        raise SpecError(f"{what}: bad name {text!r}")


def _keys(entry, required, optional=(), what=""):
    keys = set(entry)
    missing = set(required) - keys
    extra = keys - set(required) - set(optional)
    if missing or extra:
        raise SpecError(f"{what}: missing {sorted(missing)}, "
                        f"unexpected {sorted(extra)}")


def reader_path(metric: str) -> Path:
    return BENCH / "metrics" / f"{metric}.py"


def config_module_path(config: str) -> Path:
    return BENCH / "configs" / f"{config}.py"


def traffic_path(traffic: str) -> Path:
    return BENCH / "traffic" / f"{traffic}.json"


def limits_path(cell: str) -> Path:
    return BENCH / "limits" / f"{cell}.json"


def validate(spec: dict) -> dict:
    """Raise :class:`SpecError` where ``spec`` breaks a rule; return it."""
    _keys(spec, ("command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"), what="BENCHMARK.json")
    if not (isinstance(spec["run_seconds"], int)
            and 1 <= spec["run_seconds"] <= 51):
        raise SpecError("run_seconds: a whole number from 1 to 51")
    configs = {}
    for c in spec["configs"]:
        _keys(c, ("name", "source", "file", "reduced", "why"),
              what=f"config {c.get('name')}")
        _name(c["name"], "config name")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")
        if c["name"] in configs:
            raise SpecError(f"config {c['name']} given twice")
        if not (ROOT / c["file"]).is_file():
            raise SpecError(f"config {c['name']}: no file {c['file']}")
        if not config_module_path(c["name"]).is_file():
            raise SpecError(f"config {c['name']}: no builder "
                            f"{config_module_path(c['name'])}")
        configs[c["name"]] = c
    cells, pairs = {}, set()
    for w in spec["workloads"]:
        _keys(w, ("name", "config", "traffic", "chips", "why"),
              what=f"workload {w.get('name')}")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        _line(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips is 1 or 4")
        if w["config"] not in configs:
            raise SpecError(f"workload {w['name']}: unknown config")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"workload {w['name']}: pair given twice")
        if w["name"] in cells:
            raise SpecError(f"workload {w['name']} given twice")
        if not traffic_path(w["traffic"]).is_file():
            raise SpecError(f"workload {w['name']}: no traffic file "
                            f"{traffic_path(w['traffic'])}")
        if not limits_path(w["name"]).is_file():
            raise SpecError(f"workload {w['name']}: no limits file "
                            f"{limits_path(w['name'])}")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
    used = {w["config"] for w in spec["workloads"]}
    if used != set(configs):
        raise SpecError(f"configs used by no cell: {set(configs) - used}")
    names = set()
    e2e = {}
    for kind, entries in (("end_to_end", spec["end_to_end"]),
                          ("per_layer", spec["per_layer"])):
        for m in entries:
            if kind == "end_to_end":
                _keys(m, ("name", "unit", "better", "bound", "source"),
                      ("workloads",), what=f"metric {m.get('name')}")
                if m["source"] not in SOURCES_E2E:
                    raise SpecError(f"metric {m['name']}: source")
                if not 0 < m["bound"] <= 0.25:
                    raise SpecError(f"metric {m['name']}: bound")
                e2e[m["name"]] = m
            else:
                _keys(m, ("name", "unit", "better", "source", "layer",
                          "moves", "workloads"),
                      what=f"metric {m.get('name')}")
                if m["source"] not in SOURCES:
                    raise SpecError(f"metric {m['name']}: source")
                _line(m["layer"], f"metric {m['name']} layer")
                if m["moves"] not in e2e:
                    raise SpecError(f"metric {m['name']}: moves "
                                    f"{m['moves']!r}, no end-to-end metric")
            _name(m["name"], "metric name")
            if not (isinstance(m["unit"], str) and UNIT.match(m["unit"])):
                raise SpecError(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"metric {m['name']}: better")
            if m["name"] in names:
                raise SpecError(f"metric {m['name']} given twice")
            for c in m.get("workloads", ()):
                if c not in cells:
                    raise SpecError(f"metric {m['name']}: unknown cell {c}")
            if not reader_path(m["name"]).is_file():
                raise SpecError(f"metric {m['name']}: no reader "
                                f"{reader_path(m['name'])}")
            names.add(m["name"])
    if "setup_s" not in e2e:
        raise SpecError("end_to_end has no setup_s")
    for m in spec["per_layer"]:
        moved = e2e[m["moves"]].get("workloads", list(cells))
        if set(m["workloads"]) - set(moved):
            raise SpecError(f"metric {m['name']}: a cell of its workloads "
                            f"does not report {m['moves']}")
    for cell in cells:
        reported = [m["name"] for m in spec["end_to_end"]
                    if cell in m.get("workloads", cells)]
        layered = [m["name"] for m in spec["per_layer"]
                   if cell in m["workloads"]]
        if len(reported) < 2 or not layered:
            raise SpecError(f"cell {cell}: needs setup_s, another "
                            "end-to-end metric and a per-layer metric")
    return spec


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return validate(json.load(f))


def cell_metrics(spec: dict, cell: str, traced: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced."""
    entries = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in entries
            if cell in m.get("workloads", [w["name"]
                                           for w in spec["workloads"]])]


def load_module(path: Path, name: str):
    """Import the Python file at ``path`` (its name may hold ``.``/``-``)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)
