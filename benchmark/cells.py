"""BENCHMARK.json and the data files a cell names.

A cell (`workloads` entry) names a config, a traffic mix and its chips;
the files are found by those names:

    <data_root>/configs/<config>.json        sizes, flags, guarantees
    <data_root>/traffic/<traffic>.json       campaign kind and its parameters
    <data_root>/campaigns/<kind>.py          how one campaign of that kind runs
    <data_root>/layer_metrics/<metric>.py    one reader per per-layer metric
    <data_root>/peaks.json                   device peaks by device_kind

Nothing here lists cells, configs or metrics: a later PR adds files and
BENCHMARK.json entries and edits no file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

DATA_ROOT = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(DATA_ROOT)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple
    data_root: str

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise BenchmarkError(f"{path}: {exc}") from exc


def load_benchmark(path: str | None = None) -> dict:
    return load_json(path or os.path.join(REPO_ROOT, "BENCHMARK.json"))


def reports(metric: dict, workload: str) -> bool:
    """A metric with no `workloads` key is reported by every cell."""
    return "workloads" not in metric or workload in metric["workloads"]


def data_file(data_root: str, group: str, name: str, ext: str) -> str:
    if not NAME_RE.match(name):
        raise BenchmarkError(f"{group} name {name!r} is not a valid name")
    path = os.path.join(data_root, group, name + ext)
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {group} file {path}")
    return path


def load_module(path: str, tag: str):
    """A campaign kind or a metric reader, loaded from its file (the
    file may live in any data root, a test's temporary one included)."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{tag}_{os.path.basename(path)[:-3]}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench: dict | None = None,
              data_root: str | None = None) -> Cell:
    bench = bench or load_benchmark()
    data_root = data_root or DATA_ROOT
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in bench['workloads']]})"
        )
    config = load_json(data_file(data_root, "configs", entry["config"], ".json"))
    traffic = load_json(data_file(data_root, "traffic", entry["traffic"], ".json"))
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if reports(m, workload)),
        data_root=data_root,
    )


def load_campaign(cell: Cell):
    return load_module(
        data_file(cell.data_root, "campaigns", cell.kind, ".py"), "campaign"
    )


def load_reader(cell: Cell, metric: str):
    return load_module(
        data_file(cell.data_root, "layer_metrics", metric, ".py"), "metric"
    )


def checked_configs(traffic: dict) -> list:
    """The configurations `pool_check.py` ran the file's `slots` under:
    `slots_checked.config`, one name or a list of names."""
    config = (traffic.get("slots_checked") or {}).get("config", [])
    return [config] if isinstance(config, str) else list(config)


def unchecked_ranges(cell: Cell) -> str | None:
    """Why a sweep cell could run a seed range nobody has run whole under
    its configuration (a lane such a range loses is counted `failed`, so
    the cell's failed share would move with the program's rate); None
    where every range it can reach is a checked one."""
    slots = cell.traffic.get("slots")
    if not slots or len(slots) < int(cell.traffic["pool"]):
        return (f"traffic {cell.traffic_name} names no checked seed ranges "
                f"(`slots`, at least its pool of {cell.traffic['pool']}): run "
                f"pool_check.py --config {cell.config_name} on its candidates "
                f"and list the clean ones")
    if cell.config_name not in checked_configs(cell.traffic):
        return (f"traffic {cell.traffic_name}'s ranges were checked under "
                f"{checked_configs(cell.traffic)}, not under {cell.config_name}: "
                f"a new configuration brings a traffic file checked under it")
    return None


def load_peaks(cell: Cell, device_kind: str) -> dict:
    peaks = load_json(os.path.join(cell.data_root, "peaks.json"))
    if device_kind not in peaks:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(k for k in peaks if not k.startswith('_'))}); a "
            f"device that is not in the table is an error, not a default"
        )
    return peaks[device_kind]


def validate(bench: dict, data_root: str | None = None) -> list:
    """Every way BENCHMARK.json and its files disagree with the
    contract that this side can see; [] when there is none."""
    data_root = data_root or DATA_ROOT
    bad: list = []

    def name_ok(what, value):
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what}: {value!r} is not a name")

    e2e = {m["name"]: m for m in bench.get("end_to_end", [])}
    cells = [w["name"] for w in bench.get("workloads", [])]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if "setup_s" not in e2e:
        bad.append("end_to_end has no setup_s")
    for group in ("end_to_end", "per_layer"):
        for m in bench.get(group, []):
            name_ok(f"{group} metric", m.get("name"))
            if not UNIT_RE.match(str(m.get("unit", ""))):
                bad.append(f"{m.get('name')}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"{m.get('name')}: better {m.get('better')!r}")
            if m.get("source") not in SOURCES:
                bad.append(f"{m.get('name')}: source {m.get('source')!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"{m.get('name')}: unknown workload {w!r}")
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in bench.get(g, [])]
    if len(set(names)) != len(names):
        bad.append("two metrics share a name")
    for m in bench.get("end_to_end", []):
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric reads the host "
                       f"clock or the device trace")
        if not 0 < float(m.get("bound", 0)) <= 0.25:
            bad.append(f"{m['name']}: bound {m.get('bound')!r}")
    for c in bench.get("configs", []):
        name_ok("config", c.get("name"))
        if not os.path.isfile(os.path.join(os.path.dirname(data_root), c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in bench.get("workloads", [])):
            bad.append(f"config {c['name']} is used by no cell")
    seen_pairs = set()
    for w in bench.get("workloads", []):
        name_ok("workload", w.get("name"))
        name_ok("traffic", w.get("traffic"))
        if w.get("config") not in configs:
            bad.append(f"{w['name']}: unknown config {w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            bad.append(f"{w['name']}: chips {w.get('chips')!r}")
        if not 1 <= len(w.get("why", "")) <= 200:
            bad.append(f"{w['name']}: why has {len(w.get('why', ''))} characters")
        pair = (w.get("config"), w.get("traffic"))
        if pair in seen_pairs:
            bad.append(f"{w['name']}: pair {pair} appears twice")
        seen_pairs.add(pair)
        try:
            cell = load_cell(w["name"], bench, data_root)
            load_campaign(cell)
            for m in cell.per_layer:
                load_reader(cell, m["name"])
        except (BenchmarkError, KeyError) as exc:
            bad.append(f"{w['name']}: {exc}")
            continue
        if cell.kind == "sweep" and (why := unchecked_ranges(cell)):
            bad.append(f"{w['name']}: {why}")
        reported = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in reported or len(reported) < 2:
            bad.append(f"{w['name']}: reports {sorted(reported)} end to end")
        if not cell.per_layer:
            bad.append(f"{w['name']}: reports no per-layer metric")
        for m in cell.per_layer:
            if m.get("moves") not in reported:
                bad.append(f"{w['name']}: {m['name']} moves {m.get('moves')!r}, "
                           f"which this cell does not report")
    if len(set(cells)) != len(cells):
        bad.append("two cells share a name")
    four = sum(w.get("chips") == 4 for w in bench.get("workloads", []))
    if four > max(1, len(cells) // 2):
        bad.append(f"{four} of {len(cells)} cells ask for 4 chips")
    return bad
