"""A run whose timed path is broken underneath, at a small size on the
CPU, comes out not correct: the fault is planted where the program
produces its answer, in the output of the banked kernel launch."""
import jax
import pytest

from test_bench_correct import small_run
from repro.kernels import ops


@pytest.fixture
def plant(monkeypatch):
    """plant(mode, fault) swaps the bank-leading kernel of ``mode`` for
    one whose outputs ``fault`` alters; the jit caches are cleared on the
    way in and out, so no other test sees the planted kernel."""
    def _plant(mode, fault):
        name = f"_dima_{mode}_bank_kernel"
        orig = getattr(ops, name)
        monkeypatch.setattr(ops, name,
                            lambda *a, **k: fault(orig(*a, **k)))
        jax.clear_caches()
    yield _plant
    monkeypatch.undo()
    jax.clear_caches()


def _code_off_by_one_in_bank_0(out):
    code = out[0]
    return (code.at[0].set((code[0] + 1) % 256),) + tuple(out[1:])


def _trim_scaled(out):
    return out[:2] + (out[2] * 1.001,)


@pytest.mark.parametrize("name,mode,fault", [
    ("mf-dp-32bank.batch256", "dp", _code_off_by_one_in_bank_0),
    ("knn-md-32bank.batch256", "md", _code_off_by_one_in_bank_0),
    ("mf-dp-32bank.single", "dp", _trim_scaled),
])
def test_an_answer_altered_where_produced_is_not_correct(plant, name, mode,
                                                         fault):
    plant(mode, fault)
    r = small_run(name)
    assert not r["correct"], r["checks"]
