"""The harness finds every piece of a cell by name, and BENCHMARK.json
keeps to the benchmark's contract."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    names = [c["name"] for c in SPEC["configs"]] + CELLS
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for n in names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_is_found_whole(cell):
    c = harness.find_cell(cell)
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer
    for name, (entry, reader) in c.per_layer.items():
        assert entry["moves"] in c.end_to_end
        assert callable(reader.read)
    assert c.app.MODE == c.config["mode"]


def test_a_new_cell_is_found_by_name_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, and listed in BENCHMARK.json, reach the harness with no edit
    to any file it already has."""
    for rel in ("bench",):
        shutil.copytree(ROOT / rel, tmp_path / rel)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((ROOT / "bench/configs/knn-md-32bank.json").read_text())
    cfg["name"], cfg["n_banks"] = "knn-md-8bank", 8
    (tmp_path / "bench/configs/knn-md-8bank.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/batch16.json").write_text(json.dumps(
        {"queries_per_call": 16, "pool_calls": 2, "cal_queries": 16,
         "compared_calls": 2, "loop": "closed", "callers": 1}))
    (tmp_path / "bench/metrics/calls_per_s.batch.py").write_text(
        "def read(ctx):\n    return ctx['calls'] / ctx['window_s']\n")
    spec["configs"].append({"name": "knn-md-8bank", "source": "x",
                            "file": "bench/configs/knn-md-8bank.json",
                            "reduced": ["n_banks"], "why": "x"})
    spec["workloads"].append({"name": "knn-md-8bank.batch16",
                              "config": "knn-md-8bank",
                              "traffic": "batch16", "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "decisions_per_s":
            m["workloads"].append("knn-md-8bank.batch16")
    spec["per_layer"].append({"name": "calls_per_s.batch", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "whole step",
                              "moves": "decisions_per_s",
                              "workloads": ["knn-md-8bank.batch16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = harness.find_cell("knn-md-8bank.batch16", root=tmp_path)
    assert c.config["n_banks"] == 8
    assert c.traffic["queries_per_call"] == 16
    assert c.end_to_end == ["decisions_per_s", "setup_s"]
    assert list(c.per_layer) == ["calls_per_s.batch"]
    assert c.per_layer["calls_per_s.batch"][1].read(
        {"calls": 10, "window_s": 2.0}) == 5.0
    # the old cells are untouched, and so is every file that was there
    old = harness.find_cell("knn-md-32bank.batch256", root=tmp_path)
    assert "calls_per_s.batch" not in old.per_layer
    assert all(p.read_bytes() == b for p, b in before.items())


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "3000000001", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


@pytest.mark.parametrize("file,key,value", [
    ("bench/configs/knn-md-32bank.json", "mode", "dp"),
    ("bench/configs/knn-md-32bank.json", "dims", 512),
    ("bench/configs/knn-md-32bank.json", "word_bits", 4),
    ("bench/configs/knn-md-32bank.json", "precision", "bfloat16"),
    ("bench/configs/knn-md-32bank.json", "rows_per_decision", 48),
    ("bench/traffic/batch256.json", "loop", "open"),
    ("bench/traffic/batch256.json", "callers", 4),
])
def test_what_the_harness_does_not_run_is_refused(tmp_path, file, key,
                                                  value):
    """A configuration or traffic mix that states what the window does
    not do is refused by name, not measured as something else."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    data = json.loads((tmp_path / file).read_text())
    data[key] = value
    (tmp_path / file).write_text(json.dumps(data))
    with pytest.raises(ValueError, match=key.split("_")[0]):
        harness.find_cell("knn-md-32bank.batch256", root=tmp_path)


@pytest.mark.parametrize("cell", CELLS)
def test_the_geometry_is_read_from_the_configuration(cell):
    c = harness.find_cell(cell)
    assert c.config["n_banks"] * c.config["rows_per_bank"] == 2048
