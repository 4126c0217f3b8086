"""``correct`` on a whole run at a small size on the CPU: the program
passes, and the plain reference in bfloat16 put in its place fails."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402

#: two banks of 128 rows; few queries, so interpret mode keeps up
SIZES = {
    "batch256": {"n_banks": 2, "queries_per_call": 4, "pool_calls": 2,
                 "cal_queries": 8, "compared_calls": 4},
    "single": {"n_banks": 2, "pool_calls": 8, "cal_queries": 8,
               "compared_calls": 8},
}
CELLS = ["knn-md-32bank.batch256", "mf-dp-32bank.batch256",
         "mf-dp-32bank.single"]
SEED = 2 ** 31 + 977


def small_run(name, system_cls=harness.Program, seconds=1.0):
    cell = harness.find_cell(name)
    return harness.run(cell, SEED, seconds, False, time.perf_counter(),
                       sizes=SIZES[name.split(".")[1]],
                       system_cls=system_cls, check_chip=False)


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
