"""``BENCHMARK.json``: load it, hold it to the contract's shape, and find
each cell's files by name. The harness knows no cell, configuration or
metric by name: everything is looked up here."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_BOUND = 0.25


class ManifestError(ValueError):
    pass


def _line(text, what: str) -> None:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text or "\r" in text):
        raise ManifestError(f"{what}: 1 to 200 characters on one line")


def _name(text, what: str) -> None:
    if not isinstance(text, str) or not NAME.match(text):
        raise ManifestError(f"{what}: {text!r} is not a name")


def _keys(entry: dict, required: set, optional: set, what: str) -> None:
    if not isinstance(entry, dict):
        raise ManifestError(f"{what}: not an object")
    keys = set(entry)
    if not required <= keys or not keys <= required | optional:
        raise ManifestError(
            f"{what}: keys {sorted(keys)}, wanted {sorted(required)} "
            f"and at most {sorted(optional)}")


def _unique(names: list, what: str) -> None:
    if len(set(names)) != len(names):
        raise ManifestError(f"{what}: a name appears twice")


def validate(m: dict, repo: str | None = None) -> None:
    """Raise ManifestError unless ``m`` has the contract's shape. With
    ``repo``, every file a cell names must also be found there."""
    if not isinstance(m, dict) or set(m) != TOP_KEYS:
        raise ManifestError(f"top-level keys must be exactly {sorted(TOP_KEYS)}")
    paths = m["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        raise ManifestError("paths: 1 to 16 directories")
    for p in paths:
        if (not isinstance(p, str) or not PATH.match(p) or p.startswith("/")
                or ".." in p.split("/")):
            raise ManifestError(f"paths: {p!r}")
    cmd = m["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        raise ManifestError("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ManifestError(f"command: {word!r} leaves the repo")
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        raise ManifestError("run_seconds: a whole number from 1 to 51")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    configs = m["configs"]
    if not isinstance(configs, list) or not 1 <= len(configs) <= 24:
        raise ManifestError("configs: 1 to 24")
    files = []
    for c in configs:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(), "config")
        _name(c["name"], "config name")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if (not isinstance(c["file"], str) or not PATH.match(c["file"])
                or not under_paths(c["file"])):
            raise ManifestError(f"config file {c['file']!r} not under paths")
        files.append(c["file"])
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16:
            raise ManifestError("reduced: at most 16 keys")
        for k in c["reduced"]:
            _name(k, "reduced key")
    _unique([c["name"] for c in configs], "configs")
    _unique(files, "config files")

    cells = m["workloads"]
    if not isinstance(cells, list) or not 2 <= len(cells) <= 24:
        raise ManifestError("workloads: 2 to 24 cells")
    config_names = {c["name"] for c in configs}
    for w in cells:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(), "cell")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"cell {k}")
        _line(w["why"], "cell why")
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            raise ManifestError("chips: 1 or 4")
        if w["config"] not in config_names:
            raise ManifestError(f"cell {w['name']}: no config {w['config']!r}")
    _unique([w["name"] for w in cells], "workloads")
    _unique([(w["config"], w["traffic"]) for w in cells],
            "a pair of configuration and traffic")
    used = {w["config"] for w in cells}
    if used != config_names:
        raise ManifestError(f"configs no cell uses: {sorted(config_names - used)}")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        raise ManifestError("too many cells on four chips")
    cell_names = {w["name"] for w in cells}

    e2e, layers = m["end_to_end"], m["per_layer"]
    if not isinstance(e2e, list) or not 1 <= len(e2e) <= 16:
        raise ManifestError("end_to_end: 1 to 16 metrics")
    if not isinstance(layers, list) or not 1 <= len(layers) <= 128:
        raise ManifestError("per_layer: 1 to 128 metrics")
    for x in e2e:
        _keys(x, {"name", "unit", "better", "bound", "source"}, {"workloads"},
              "end-to-end metric")
        if x["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{x['name']}: an end-to-end metric is taken "
                                f"by the benchmark itself")
        b = x["bound"]
        if (isinstance(b, bool) or not isinstance(b, (int, float))
                or not 0.01 <= b <= MAX_BOUND):
            raise ManifestError(f"{x['name']}: bound {b!r} outside [0.01, "
                                f"{MAX_BOUND}]")
    e2e_names = {x["name"] for x in e2e}
    if "setup_s" not in e2e_names:
        raise ManifestError("end_to_end needs setup_s")
    for x in layers:
        _keys(x, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, "per-layer metric")
        _line(x["layer"], "layer")
        if x["source"] not in SOURCES:
            raise ManifestError(f"{x['name']}: source {x['source']!r}")
        if x["moves"] not in e2e_names:
            raise ManifestError(f"{x['name']}: moves {x['moves']!r}, which "
                                f"is no end-to-end metric")
    for x in e2e + layers:
        _name(x["name"], "metric name")
        if not isinstance(x["unit"], str) or not UNIT.match(x["unit"]):
            raise ManifestError(f"{x['name']}: unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            raise ManifestError(f"{x['name']}: better {x['better']!r}")
        for w in x.get("workloads", []):
            if w not in cell_names:
                raise ManifestError(f"{x['name']}: no cell {w!r}")
    _unique([x["name"] for x in e2e + layers], "metrics")
    for w in cells:
        mine = [x["name"] for x in metrics_of(m, w["name"], "end_to_end")]
        if "setup_s" not in mine or len(mine) < 2:
            raise ManifestError(f"cell {w['name']}: setup_s and one more "
                                f"end-to-end metric")
        per = metrics_of(m, w["name"], "per_layer")
        if not per:
            raise ManifestError(f"cell {w['name']}: no per-layer metric")
        for x in per:
            if x["moves"] not in mine:
                raise ManifestError(
                    f"{x['name']} moves {x['moves']}, which cell "
                    f"{w['name']} does not report")
    if len(json.dumps(m)) > 64 * 1024:
        raise ManifestError("larger than 64 KiB")
    if repo is not None:
        bench = bench_dir(m, repo)
        for w in cells:
            cell_files(m, w["name"], repo)
        for x in layers:
            reader_file(bench, x["name"])


def metrics_of(m: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` that ``cell`` reports: those with no
    ``workloads`` key, and those that list it."""
    return [x for x in m[group]
            if "workloads" not in x or cell in x["workloads"]]


def bench_dir(m: dict, repo: str) -> str:
    return os.path.join(repo, m["paths"][0])


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _overlaid(real: dict, path: str) -> dict:
    """``real`` with the rehearsal file at ``path`` (where there is one)
    laid over it: a value that is an object replaces keys of the real
    object, anything else replaces the real value."""
    if not os.path.exists(path):
        return real
    out = dict(real)
    for k, v in load(path).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def cell_files(m: dict, cell: str, repo: str, rehearsal: bool = False) -> dict:
    """-> the cell's entry, its configuration (file, INI template) and
    its traffic (file, driver file), found by name. A ``rehearsal`` runs
    the same files at toy sizes: ``rehearsal/configs/<file>`` and
    ``rehearsal/traffic/<file>`` hold only what differs
    (``ini_replace`` maps text of the INI to its replacement)."""
    w = next((w for w in m["workloads"] if w["name"] == cell), None)
    if w is None:
        raise ManifestError(f"no cell {cell!r}")
    c = next(c for c in m["configs"] if c["name"] == w["config"])
    bench = bench_dir(m, repo)
    config_path = os.path.join(repo, c["file"])
    config = load(config_path)
    ini_path = os.path.join(os.path.dirname(config_path), config["ini"])
    traffic_path = next(
        (p for p in (os.path.join(bench, "traffic", w["traffic"] + s)
                     for s in TRAFFIC_SUFFIXES) if os.path.exists(p)), None)
    if traffic_path is None:
        raise ManifestError(f"no traffic file for {w['traffic']!r}")
    if not traffic_path.endswith(".json"):
        raise ManifestError("the general generator reads .json parameters")
    traffic = load(traffic_path)
    driver_path = os.path.join(bench, "drivers", traffic["driver"] + ".py")
    for p in (ini_path, driver_path):
        if not os.path.exists(p):
            raise ManifestError(f"cell {cell}: {p} not found")
    with open(ini_path) as fh:
        ini = fh.read()
    if rehearsal:
        toy = os.path.join(bench, "rehearsal")
        config = _overlaid(config, os.path.join(
            toy, "configs", os.path.basename(config_path)))
        traffic = _overlaid(traffic, os.path.join(
            toy, "traffic", os.path.basename(traffic_path)))
        for old, new in config.get("ini_replace", {}).items():
            ini = ini.replace(old, new)
    return {"cell": w, "config_entry": c, "config": config, "ini": ini,
            "traffic": traffic, "driver_path": driver_path}


def reader_file(bench: str, metric: str) -> str:
    for suffix in (".json", ".py"):
        p = os.path.join(bench, "layers", metric + suffix)
        if os.path.exists(p):
            return p
    raise ManifestError(f"per-layer metric {metric!r} has no reader file")
