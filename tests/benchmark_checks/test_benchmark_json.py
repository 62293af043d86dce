"""BENCHMARK.json against the contract, as far as this side can see it.
Nothing here pins the list of cells, configs or metrics: a later PR's
additions pass unedited."""

import copy
import json
import os

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"}, {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def test_the_file_has_exactly_the_contracts_keys_and_is_small():
    assert set(BENCH) == TOP_KEYS
    path = os.path.join(cells.REPO_ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    for group, (need, may) in ENTRY_KEYS.items():
        assert 1 <= len(BENCH[group])
        for entry in BENCH[group]:
            assert need <= set(entry) <= need | may, (group, entry.get("name"))


def test_every_name_unit_and_line_is_well_formed():
    for group in ENTRY_KEYS:
        for entry in BENCH[group]:
            assert cells.NAME_RE.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry:
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
            if "unit" in entry:
                assert cells.UNIT_RE.match(entry["unit"]), entry["unit"]
    for c in BENCH["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert cells.NAME_RE.match(key)
            assert not key.endswith(("_dim", "_rank"))
    assert all(1 <= len(word) <= 200 for word in BENCH["command"])
    assert len(BENCH["command"]) <= 32


def test_every_file_a_cell_names_resolves_and_every_arrow_lands():
    assert cells.validate(BENCH) == []


def test_the_paths_hold_the_benchmark_and_every_file_name_is_plain():
    for rel in BENCH["paths"]:
        assert not rel.startswith("/") and ".." not in rel.split("/")
        top = os.path.join(cells.REPO_ROOT, rel)
        assert os.path.isdir(top)
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                assert cells.NAME_RE.match(fn), os.path.join(dirpath, fn)
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


def test_a_full_check_fits_the_drivers_day():
    """2 + 14 x 24 runs of run_seconds + 60 s, 2 x 90 s a cell to compile,
    1200 s spare, within 43200 s — at the full 24 cells, since later PRs
    add cells and may not change run_seconds."""
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_config_file_states_its_source_cut_and_guarantees():
    for c in BENCH["configs"]:
        doc = cells.load_json(os.path.join(cells.REPO_ROOT, c["file"]))
        assert doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        assert doc["guarantees"] and doc["flags"]["batch"] >= 1
        assert doc["check"]["sample_lanes"] >= 64


def test_every_traffic_file_is_data_for_the_one_generator():
    for w in BENCH["workloads"]:
        t = cells.load_cell(w["name"]).traffic
        assert {"kind", "seeds", "stride", "pool", "base_seed",
                "warmup_seed"} <= set(t)
        # campaigns never overlap, the warm-up and the lane sample sit
        # below them, and every seed fits the engine's uint32
        assert t["stride"] >= 2 * t["seeds"]
        assert t["warmup_seed"] + t["stride"] <= t["base_seed"]
        assert t["base_seed"] + 64 * t["stride"] < 2**32


def doctored(edit) -> list:
    bench = copy.deepcopy(BENCH)
    edit(bench)
    return cells.validate(bench)


def test_validation_sees_the_faults_it_is_there_for():
    def wrong_arrow(b):
        m = next(m for m in b["per_layer"] if m["moves"] != "setup_s")
        other = next(e["name"] for e in b["end_to_end"]
                     if e["name"] not in ("setup_s", m["moves"]))
        m["moves"] = other

    def unknown_traffic(b):
        b["workloads"][0]["traffic"] = "no_such_mix"

    def bad_unit(b):
        b["per_layer"][0]["unit"] = "tokens per second"

    def no_reader(b):
        b["per_layer"].append(dict(b["per_layer"][0], name="never_written"))

    def two_fours(b):
        for w in b["workloads"]:
            w["chips"] = 4

    for edit in (wrong_arrow, unknown_traffic, bad_unit, no_reader, two_fours):
        assert doctored(edit), edit.__name__


def test_an_unknown_cell_or_device_kind_is_an_error_not_a_default():
    with pytest.raises(cells.BenchmarkError):
        cells.load_cell("no_such_cell")
    cell = cells.load_cell(BENCH["workloads"][0]["name"])
    assert cells.load_peaks(cell, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.BenchmarkError):
        cells.load_peaks(cell, "TPU v9 imaginary")
