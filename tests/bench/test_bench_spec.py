"""BENCHMARK.json against the rules it is checked by, and every file it
names present: the per-cell data, a reader per metric, a driver per mix."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _e2e_of(spec, cell):
    return {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}


def test_top_level_shape(spec):
    assert set(spec) == KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(spec["paths"]) <= 16 and all(PATH.match(p) and ".." not in p for p in spec["paths"])
    assert 1 <= len(spec["command"]) <= 32
    assert all(not w.startswith("/") and ".." not in w for w in spec["command"])
    for w in spec["command"]:
        if w.endswith(".py"):
            assert any(w.startswith(p + "/") for p in spec["paths"]), w
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51


def test_full_check_fits_with_24_cells(spec):
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_keys(spec):
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for section in ("end_to_end", "per_layer"):
        for m in spec[section]:
            allowed = {"name", "unit", "better", "source"} | (
                {"bound"} if section == "end_to_end" else {"layer", "moves"}
            ) | {"workloads"}
            assert set(m) <= allowed and set(m) >= allowed - {"workloads"}, m
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in spec[group]]
        assert all(NAME.match(n) for n in ns) and len(ns) == len(set(ns))
    for text in [c["why"] for c in spec["configs"] + spec["workloads"]] + [m["layer"] for m in spec["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_sources_and_bounds(spec):
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_every_cell_reports_what_it_must(spec):
    for w in spec["workloads"]:
        e2e = _e2e_of(spec, w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]]) for m in spec["per_layer"])


def test_moves_names_an_end_to_end_metric_of_each_of_its_cells(spec):
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        for c in m.get("workloads", sorted(cells)):
            assert c in cells
            assert m["moves"] in _e2e_of(spec, c), (m["name"], c)


def test_roofline_and_mfu_metrics_are_shares(spec):
    for m in spec["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    # every kernel roofline has a whole-step mfu beside it, moving the same metric
    for m in spec["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"] for o in spec["per_layer"])


def test_at_most_half_the_cells_take_four_chips(spec):
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_files_found_by_name(spec):
    confs = {c["name"]: c for c in spec["configs"]}
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in spec["workloads"]:
        used.add(w["config"])
        path = os.path.join(ROOT, "bench", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "bench", "drivers", mix["driver"] + ".py"))
    assert used == set(confs)
    for c in spec["configs"]:
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(ROOT, "bench", "reference", cfg["reference"] + ".py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"))


def test_configuration_files_are_what_the_program_serves(spec):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config

    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        reg = get_config(cfg["registry"])
        for k, v in cfg["arch"].items():
            if k not in c["reduced"]:
                assert getattr(reg, k) == v, (c["name"], k)
