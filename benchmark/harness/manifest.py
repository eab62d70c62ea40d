"""BENCHMARK.json and the data files it names: loading, and the contract's
rules as checks (run by the tests, and by the harness before a run)."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "experts_per_tok")


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _line(text, what, errors):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def check_manifest(m: dict, root: str = ROOT) -> list:
    """Every breach of the contract's static rules, as text; [] if none."""
    errors: list = []
    if set(m) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return errors
    if not (isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32):
        errors.append("command: a list of 1 to 32 strings")
    for word in m["command"]:
        _line(word, f"command word {word!r}", errors)
        if word.startswith("/") or ".." in word.split("/"):
            errors.append(f"command word {word!r} leaves the repo")
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH_RE.match(p) for p in paths)):
        errors.append("paths: 1 to 16 relative directories")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    def unique(names, what):
        for n in names:
            if not (isinstance(n, str) and NAME_RE.match(n)):
                errors.append(f"{what} name {n!r} breaks the name rule")
        if len(set(names)) != len(names):
            errors.append(f"{what}: a name appears twice")

    configs = m["configs"]
    if not 1 <= len(configs) <= 24:
        errors.append("configs: 1 to 24")
    unique([c.get("name") for c in configs], "config")
    files = []
    for c in configs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _line(c["source"], f"config {c['name']} source", errors)
        _line(c["why"], f"config {c['name']} why", errors)
        files.append(c["file"])
        if not (PATH_RE.match(c["file"]) and under_paths(c["file"])):
            errors.append(f"config {c['name']}: file outside paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            errors.append(f"config {c['name']}: no file {c['file']}")
        if len(c["reduced"]) > 16:
            errors.append(f"config {c['name']}: over 16 reduced keys")
        for key in c["reduced"]:
            if not NAME_RE.match(key):
                errors.append(f"config {c['name']}: reduced key {key!r}")
            low = key.lower()
            if (low.endswith("_dim") or low.endswith("_rank")
                    or any(w in low for w in WIDTH_WORDS)):
                errors.append(f"config {c['name']}: reduced names the "
                              f"width {key!r}")
    if len(set(files)) != len(files):
        errors.append("configs: two configurations share a file")

    cells = m["workloads"]
    if not 1 <= len(cells) <= 24:
        errors.append("workloads: 1 to 24")
    unique([w.get("name") for w in cells], "workload")
    config_names = {c.get("name") for c in configs}
    pairs = set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        _line(w["why"], f"workload {w['name']} why", errors)
        if w["config"] not in config_names:
            errors.append(f"workload {w['name']}: unknown config")
        if not NAME_RE.match(str(w["traffic"])):
            errors.append(f"workload {w['name']}: traffic name")
        if w["chips"] not in (1, 4):
            errors.append(f"workload {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            errors.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    used = {w.get("config") for w in cells}
    for c in config_names - used:
        errors.append(f"config {c}: used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        errors.append("over a quarter of the cells ask for 4 chips")

    cell_names = [w.get("name") for w in cells]
    e2e, layer = m["end_to_end"], m["per_layer"]
    if not 1 <= len(e2e) <= 16:
        errors.append("end_to_end: 1 to 16")
    if not 1 <= len(layer) <= 128:
        errors.append("per_layer: 1 to 128")
    unique([x.get("name") for x in e2e + layer], "metric")
    e2e_names = {x.get("name") for x in e2e}
    if "setup_s" not in e2e_names:
        errors.append("end_to_end: setup_s is missing")
    for x in e2e:
        if not set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or not {
                "name", "unit", "better", "bound", "source"} <= set(x):
            errors.append(f"metric {x.get('name')}: keys {sorted(x)}")
            continue
        if x["source"] not in ("host_clock", "device_trace"):
            errors.append(f"metric {x['name']}: end-to-end source")
        if not 0 < x["bound"] <= 0.1:
            errors.append(f"metric {x['name']}: bound in (0, 0.1]")
    for x in layer:
        if not set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} or not {
                "name", "unit", "better", "source", "layer",
                "moves"} <= set(x):
            errors.append(f"metric {x.get('name')}: keys {sorted(x)}")
            continue
        _line(x["layer"], f"metric {x['name']} layer", errors)
        if x["moves"] not in e2e_names:
            errors.append(f"metric {x['name']}: moves an unknown metric")
        if x["source"] not in SOURCES:
            errors.append(f"metric {x['name']}: source")
    for x in e2e + layer:
        if not UNIT_RE.match(str(x.get("unit", ""))):
            errors.append(f"metric {x.get('name')}: unit")
        if x.get("better") not in ("lower", "higher"):
            errors.append(f"metric {x.get('name')}: better")
        for w in x.get("workloads", []):
            if w not in cell_names:
                errors.append(f"metric {x.get('name')}: unknown cell {w}")
    for w in cell_names:
        reports = [x for x in e2e
                   if w in x.get("workloads", cell_names)]
        if not any(x["name"] == "setup_s" for x in reports):
            errors.append(f"cell {w}: no setup_s")
        if len(reports) < 2:
            errors.append(f"cell {w}: no end-to-end metric beside setup_s")
        if not any(w in x.get("workloads", cell_names) for x in layer):
            errors.append(f"cell {w}: no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        errors.append("BENCHMARK.json is over 64 KiB")
    return errors


def cell_of(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in m['workloads']]})")


def config_of(m: dict, cell: dict, root: str = ROOT) -> dict:
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    return load_json(os.path.join(root, entry["file"]))


def traffic_of(cell: dict) -> dict:
    return load_json(os.path.join(BENCH, "traffic",
                                  cell["traffic"] + ".json"))


def limits_of(cell: dict) -> dict:
    return load_json(os.path.join(BENCH, "limits", cell["name"] + ".json"))


def metrics_for(m: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [x for x in m[kind]
            if cell_name in x.get("workloads", [cell_name])]
