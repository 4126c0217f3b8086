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
from test_bench_correct import SEED, cell_names, small_run, small_sizes  # noqa: E402,E501

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = cell_names()


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


def add_cell(root, config, reduced, traffic_name, traffic):
    """A copy of ``bench/`` under ``root`` with one more cell, made of a
    new configuration, a new traffic mix and a new per-layer reader, and
    listed in a copy of BENCHMARK.json.  Returns the cell's name and the
    bytes of every file that the copy held before."""
    shutil.copytree(ROOT / "bench", root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    name = f"{config['name']}.{traffic_name}"
    file = f"bench/configs/{config['name']}.json"
    (root / file).write_text(json.dumps(config))
    (root / "bench/traffic" / f"{traffic_name}.json").write_text(json.dumps(
        {**traffic, "loop": "closed", "callers": 1}))
    (root / "bench/metrics/calls_per_s.batch.py").write_text(
        "def read(ctx):\n    return ctx['calls'] / ctx['window_s']\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": config["name"], "source": "x",
                            "file": file, "reduced": reduced, "why": "x"})
    spec["workloads"].append({"name": name, "config": config["name"],
                              "traffic": traffic_name, "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "decisions_per_s":
            m["workloads"].append(name)
    spec["per_layer"].append({"name": "calls_per_s.batch", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "whole step",
                              "moves": "decisions_per_s",
                              "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return name, before


def test_a_new_cell_is_found_by_name_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, and listed in BENCHMARK.json, reach the harness with no edit
    to any file it already has."""
    cfg = json.loads((ROOT / "bench/configs/knn-md-32bank.json").read_text())
    cfg["name"], cfg["n_banks"] = "knn-md-8bank", 8
    name, before = add_cell(tmp_path, cfg, ["n_banks"], "batch16",
                            {"queries_per_call": 16, "pool_calls": 2,
                             "cal_queries": 16, "compared_calls": 2})

    c = harness.find_cell(name, root=tmp_path)
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


#: the paper's 32-bank scenario: 32 banks of 64 stored vectors
PAPER_ROWS = {"mf-dp-32bank": 2048, "knn-md-32bank": 2048}


def check_geometry(name, root=ROOT):
    """Set-up at the cell's small size stores the rows and gives the
    decisions per call that the cell's configuration file states."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    w = next(w for w in spec["workloads"] if w["name"] == name)
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / c["file"]).read_text())
    rows = cfg["n_banks"] * cfg["rows_per_bank"]
    assert rows % cfg["rows_per_decision"] == 0
    if w["config"] in PAPER_ROWS:
        assert rows == PAPER_ROWS[w["config"]]
    cell = harness.find_cell(name, root=root)
    sizes = small_sizes(cell)
    st = harness.setup(cell, SEED, sizes)
    M = sizes["n_banks"] * cfg["rows_per_bank"]
    per_decision = (M if cfg["rows_per_decision"] == rows
                    else cfg["rows_per_decision"])
    assert st["M"] == M
    assert st["per_call"] == st["B"] * M // per_decision
    assert st["B"] == sizes.get("queries_per_call",
                                cell.traffic["queries_per_call"])
    return st


@pytest.mark.parametrize("cell", CELLS)
def test_the_geometry_is_read_from_the_configuration(cell):
    check_geometry(cell)


def test_a_cell_of_another_geometry_needs_only_new_files(tmp_path):
    """A configuration of six banks with one decision over all its rows
    and a traffic mix of 128 queries per call, added as new files, pass
    the geometry contract and the CPU check of ``correct`` (the program
    is, the bfloat16 control is not), with no byte changed in any file
    that was there."""
    cfg = json.loads((ROOT / "bench/configs/knn-md-32bank.json").read_text())
    cfg.update(name="knn-md-6bank-global", n_banks=6, rows_per_decision=384)
    name, before = add_cell(tmp_path, cfg, ["n_banks", "rows_per_decision"],
                            "batch128",
                            {"queries_per_call": 128, "pool_calls": 4,
                             "cal_queries": 256, "compared_calls": 4})

    c = harness.find_cell(name, root=tmp_path)
    assert c.end_to_end == ["decisions_per_s", "setup_s"]
    assert list(c.per_layer) == ["calls_per_s.batch"]
    assert name in cell_names(tmp_path)
    st = check_geometry(name, root=tmp_path)
    assert st["per_call"] == st["B"] == 4       # one vote over all rows
    ok = small_run(name, root=tmp_path)
    assert ok["correct"], ok["checks"]
    control = small_run(name, harness.Control, root=tmp_path)
    assert not control["correct"], control["checks"]
    assert all(p.read_bytes() == b for p, b in before.items())
