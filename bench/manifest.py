"""``BENCHMARK.json``: loading, checking, and finding a cell's files.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name the manifest gives it:

  * ``bench/configs/<config>.json`` (sizes) and ``<config>.py`` (its plain
    reference and the program entries that run it);
  * ``bench/traffic/<traffic>.json`` (parameters), run by
    ``bench/drivers/<kind>.py`` for the file's ``kind``;
  * ``bench/checks/<workload>.json``: the limits of the numbers compared
    (its ``rehearse`` block holds the limits of the CPU rehearsal's tiny
    widths, for ``bench/tests/test_checks.py`` alone);
  * ``bench/metrics/<family>.py`` for a per-layer metric, where the family
    is the metric's name up to the first ``.``.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import sys

BENCH = pathlib.Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E_SOURCES = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load(path: pathlib.Path) -> dict:
    m = json.loads(pathlib.Path(path).read_text())
    validate(m)
    return m


def _line(s, what):
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s \
            or "\t" in s:
        raise ManifestError(f"{what} must be 1-200 characters on one line")


def validate(m: dict) -> None:
    """The manifest's own rules: names, units, sources, what each metric
    moves, and the share of four-chip cells."""
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(m) != keys:
        raise ManifestError(f"keys must be exactly {sorted(keys)}")
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            if not NAME.match(e["name"]):
                raise ManifestError(f"bad name {e['name']!r}")
            if e["name"] in names:
                raise ManifestError(f"duplicate name {e['name']!r}")
            names.add(e["name"])
    configs = {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        _line(c["why"], f"config {c['name']} why")
        _line(c["source"], f"config {c['name']} source")
        for k in c["reduced"]:
            if not NAME.match(k):
                raise ManifestError(f"bad reduced key {k!r}")
    pairs = set()
    for w in m["workloads"]:
        _line(w["why"], f"workload {w['name']} why")
        if w["config"] not in configs:
            raise ManifestError(f"{w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            raise ManifestError(f"{w['name']}: pair {pair} appears twice")
        pairs.add(pair)
    four = sum(w["chips"] == 4 for w in m["workloads"])
    if four > max(1, len(m["workloads"]) // 2):
        raise ManifestError(f"{four} four-chip cells of "
                            f"{len(m['workloads'])}: at most half")
    cells = {w["name"] for w in m["workloads"]}
    e2e = {}
    for e in m["end_to_end"]:
        _metric_common(e)
        if e["source"] not in E2E_SOURCES:
            raise ManifestError(f"{e['name']}: end-to-end source must be one "
                                f"of {E2E_SOURCES}")
        if not 0.01 <= e["bound"] <= 0.25:
            raise ManifestError(f"{e['name']}: bound out of [0.01, 0.25]")
        e2e[e["name"]] = set(e.get("workloads", cells))
    if "setup_s" not in e2e:
        raise ManifestError("setup_s must be an end-to-end metric")
    for p in m["per_layer"]:
        _metric_common(p)
        _line(p["layer"], f"{p['name']} layer")
        if p["moves"] not in e2e:
            raise ManifestError(f"{p['name']} moves unknown {p['moves']}")
        missing = set(p.get("workloads", e2e[p["moves"]])) - e2e[p["moves"]]
        if missing:
            raise ManifestError(f"{p['name']}: cells {sorted(missing)} do not "
                                f"report {p['moves']}")
    for cell in cells:
        reports = [n for n, ws in e2e.items() if cell in ws]
        if "setup_s" not in reports or len(reports) < 2:
            raise ManifestError(f"{cell}: needs setup_s and another "
                                f"end-to-end metric")
        if not per_layer_for(m, cell):
            raise ManifestError(f"{cell}: no per-layer metric")


def _metric_common(e):
    if not UNIT.match(e["unit"]):
        raise ManifestError(f"{e['name']}: bad unit {e['unit']!r}")
    if e["better"] not in ("lower", "higher"):
        raise ManifestError(f"{e['name']}: better must be lower|higher")
    if e["source"] not in SOURCES:
        raise ManifestError(f"{e['name']}: unknown source {e['source']}")


def cell(m: dict, workload: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == workload:
            return w
    raise ManifestError(f"no workload {workload!r} in BENCHMARK.json")


def end_to_end_for(m: dict, workload: str) -> list[dict]:
    return [e for e in m["end_to_end"]
            if workload in e.get("workloads", [workload])]


def per_layer_for(m: dict, workload: str) -> list[dict]:
    moved = {e["name"] for e in end_to_end_for(m, workload)}
    return [p for p in m["per_layer"] if p["moves"] in moved
            and workload in p.get("workloads", [workload])]


def config_of(m: dict, name: str) -> dict:
    for c in m["configs"]:
        if c["name"] == name:
            return c
    raise ManifestError(f"no config {name!r}")


def _rehearsed(data: dict, rehearse: bool) -> dict:
    over = data.pop("rehearse", {})
    if rehearse:
        data.update(over)
    return data


def read_json(path: pathlib.Path, rehearse: bool = False) -> dict:
    return _rehearsed(json.loads(pathlib.Path(path).read_text()), rehearse)


def import_file(path: pathlib.Path, name: str):
    """Import one file of the benchmark as a module of its own."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise ManifestError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def files_for(m: dict, workload: str, rehearse: bool = False,
              bench: pathlib.Path = BENCH) -> dict:
    """The cell's entry, configuration, traffic, limits and modules."""
    w = cell(m, workload)
    c = config_of(m, w["config"])
    cfg_file = bench.parent / c["file"]
    traffic = read_json(bench / "traffic" / f"{w['traffic']}.json", rehearse)
    return {
        "cell": w,
        "config_entry": c,
        "config": read_json(cfg_file, rehearse),
        "model": import_file(cfg_file.with_suffix(".py"),
                             f"bench_config_{w['config']}"),
        "traffic": traffic,
        "driver": import_file(bench / "drivers" / f"{traffic['kind']}.py",
                              f"bench_driver_{traffic['kind']}"),
        "limits": read_json(bench / "checks" / f"{workload}.json", rehearse),
    }


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    family = name.split(".", 1)[0]
    return import_file(bench / "metrics" / f"{family}.py",
                       f"bench_metric_{family}")
