"""``correct`` on a whole run at a small size on the CPU, for every cell
of ``BENCHMARK.json``: the program passes, and the plain reference in
bfloat16 put in its place fails."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

#: a cell's small sizes, by its traffic's queries per call: two banks of
#: the configuration's rows, and few queries, so interpret mode keeps up
N_BANKS = 2
BATCH = {"queries_per_call": 4, "pool_calls": 2, "cal_queries": 8,
         "compared_calls": 4}
SINGLE = {"pool_calls": 8, "cal_queries": 8, "compared_calls": 8}
SEED = 2 ** 31 + 977


def cell_names(root=ROOT):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    return [w["name"] for w in spec["workloads"]]


CELLS = cell_names()


def small_sizes(cell):
    """The configuration's shape on a few banks: its rows per bank, and
    its rows per decision, which span all the small cell's rows where
    they span all the configuration's."""
    cfg = cell.config
    n_banks = min(N_BANKS, cfg["n_banks"])
    sizes = {"n_banks": n_banks,
             **(SINGLE if cell.traffic["queries_per_call"] == 1 else BATCH)}
    if cfg["rows_per_decision"] == cfg["n_banks"] * cfg["rows_per_bank"]:
        sizes["rows_per_decision"] = n_banks * cfg["rows_per_bank"]
    return sizes


def small_run(name, system_cls=harness.Program, seconds=1.0, root=ROOT):
    cell = harness.find_cell(name, root=root)
    return harness.run(cell, SEED, seconds, False, time.perf_counter(),
                       sizes=small_sizes(cell), system_cls=system_cls,
                       check_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    r = small_run(name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == set(harness.find_cell(name).end_to_end)
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_is_not_correct(name):
    r = small_run(name, harness.Control)
    assert not r["correct"], r["checks"]
    assert r["checks"]["code_mismatch_share"]["value"] > \
        r["checks"]["code_mismatch_share"]["limit"]
