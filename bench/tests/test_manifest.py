"""BENCHMARK.json against the benchmark's contract: names and units, the
files and plugins each entry resolves to, and which cells report what."""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {"batch_points_per_s", "serve_points_per_s", "setup_s"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert manifest["paths"] == ["bench"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_names_units_and_texts(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and text_ok(c["source"])
        assert text_ok(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert text_ok(w["why"]) and w["chips"] in (1, 4)
        names.append(w["name"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert text_ok(m["layer"])
    assert len(names) == len(set(names))
    assert {m["name"] for m in manifest["end_to_end"]} == E2E
    assert next(m for m in manifest["end_to_end"]
                if m["name"] == "setup_s")["bound"] == 0.25


def test_every_entry_resolves_to_its_files(manifest):
    from benchlib import harness
    confs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        mix = harness.load_json(os.path.join(BENCH, "mixes",
                                             f"{w['traffic']}.json"))
        assert set(mix) == {"generator", "params"}
        assert w["config"] in confs
        used.add(w["config"])
        pairs.add((w["config"], w["traffic"]))
        assert callable(harness.plugin("traffic", mix["generator"]).make)
    assert used == set(confs)
    # A pair of configuration and traffic mix makes one cell.
    assert len(pairs) == len(manifest["workloads"])
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"].startswith("bench/")
        conf = harness.load_json(os.path.join(ROOT, c["file"]))
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) <= set(conf["reduced"])
    for m in manifest["per_layer"]:
        assert callable(harness.plugin("metrics", m["name"]).read)
    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))
    assert peaks["source"] and "TPU v5 lite" in peaks["devices"]


def test_each_cell_reports_what_its_metrics_move(manifest):
    from benchlib import harness
    layers = {}
    for w in manifest["workloads"]:
        p = harness.plan(w["name"], ROOT)
        e2e = {m["name"] for m in p.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert p.per_layer
        for m in p.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in manifest["workloads"]}


def test_a_full_check_fits_its_time(manifest):
    # 2 + 14 runs per cell at run_seconds + 60 s, 2 x 90 s of compiles
    # per cell, 1200 s spare: it must fit 43,200 s with the full 24 cells.
    s = manifest["run_seconds"]
    cells = 24
    assert (2 + 14 * cells) * (s + 60) + cells * 180 + 1200 <= 43200
