"""The benchmark is data: every file BENCHMARK.json names loads, a new
cell is found by name from new files alone, and the names, units and
``moves`` keep to the benchmark's contract."""
import hashlib
import json
import shutil

import pytest

from bench.spec import NAME_RE, ROOT, UNIT_RE, Bench

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"][:2] == ["python3", "bench/run.py"]
    assert DOC["paths"] == ["bench"]
    assert 1 <= DOC["run_seconds"] <= 51
    # a full check of 24 cells fits its time: 2 + 14 runs a cell
    assert (2 + 14 * 24) * (DOC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_cell_files_load(cell):
    b = Bench()
    c = b.cell(cell)
    assert c["config"]["name"] == c["workload"]["config"]
    assert b.module("drivers", c["traffic"]["driver"]).run
    assert b.module("refs", c["config"]["reference"]).features
    assert set(c["config"]["limits"]) == {"count_gap", "keypoint_miss",
                                          "desc_miss"}
    entry = next(e for e in DOC["configs"] if e["name"] == c["config"]["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert entry["reduced"] == c["config"]["reduced"]


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_readers_load(metric):
    reader = Bench().module("metrics", metric)
    assert callable(reader.read)
    assert reader.read({"trace": None, "setup_s": 1.0,
                        "setup_compile": {"compile_s": 0.5}}) in (
        None, 1.0, 0.5)


def test_names_and_units():
    names = [x["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in DOC[part]]
    assert len(names) == len(set(names))
    for w in DOC["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in DOC["configs"]:
        names += c["reduced"]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for n in names:
        assert NAME_RE.match(n), n
    for m in METRICS:
        assert UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) \
        <= max(1, len(DOC["workloads"]) // 2)


def test_bounds():
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]])
def test_moves_is_reported_by_each_cell(metric):
    b = Bench()
    m = next(x for x in DOC["per_layer"] if x["name"] == metric)
    cells = m.get("workloads", [w["name"] for w in DOC["workloads"]])
    for cell in cells:
        e2e = [x["name"] for x in b.metrics_for(cell, "end_to_end")]
        assert m["moves"] in e2e, (metric, cell)


def test_every_cell_reports_enough():
    b = Bench()
    for w in DOC["workloads"]:
        e2e = [x["name"] for x in b.metrics_for(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert b.metrics_for(w["name"], "per_layer")


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digest(root / "bench")
    # a later change adds a mix, a cell and a metric: files and entries
    mix = json.loads((root / "bench/traffic/xyz256.steady.json").read_text())
    mix["arrival"] = "burst"
    (root / "bench/traffic/xyz256.burst.json").write_text(json.dumps(mix))
    (root / "bench/metrics/shed_share.tile.py").write_text(
        "def read(run):\n    return run['failed'] / run['attempted']\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "xyz256.burst", "config":
                             "xyz256_service", "traffic": "xyz256.burst",
                             "chips": 1, "why": "bursts"})
    doc["per_layer"].append({"name": "shed_share.tile", "unit": "share",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler", "moves": "tile_p50_ms",
                             "workloads": ["xyz256.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    after = _digest(root / "bench")
    assert all(after[p] == h for p, h in before.items())
    b = Bench(root)
    cell = b.cell("xyz256.burst")
    assert cell["traffic"]["arrival"] == "burst"
    assert b.module("drivers", cell["traffic"]["driver"]).run
    layer = [m["name"] for m in b.metrics_for("xyz256.burst", "per_layer")]
    assert "shed_share.tile" in layer
    assert b.module("metrics", "shed_share.tile").read(
        {"failed": 1, "attempted": 4}) == 0.25
    assert "shed_share.tile" not in [
        m["name"] for m in b.metrics_for("xyz256.steady", "per_layer")]
