"""chip_smoke.py's phases at tiny sizes on CPU (Pallas in interpret
mode), and its refusal to report a result without a TPU."""
import importlib.util
import json
import os
import re

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_kernels_small(smoke, capsys):
    smoke.phase_kernels(m=256, n_banks=2, b=2, native=False)
    out = capsys.readouterr().out
    # every configuration matched the reference
    assert len(re.findall(r": 0 of \d+ codes differ", out)) == 16
    assert "1 dispatch; traced inside an enclosing jit: 0" in out


def test_phase_apps_subset(smoke):
    smoke.phase_apps(apps=("mf", "tm"))


def test_phase_apps_rejects_drift(smoke, monkeypatch):
    monkeypatch.setitem(smoke.SEED_ACC, "mf", (0.9, 1.0))
    with pytest.raises(smoke.SmokeFailure, match="mf accuracy"):
        smoke.phase_apps(apps=("mf",))


@pytest.mark.parametrize("analog", [False, True], ids=["dima", "analog_lm"])
def test_phase_serve_reduced(smoke, analog):
    smoke.phase_serve(batch=2, prompt_len=8, gen=4, analog=analog,
                      reduced=True)


def test_phase_mesh_one_device(smoke):
    smoke.phase_mesh(jax.devices()[:1], m=256, n_banks=2, b=2)


def test_phase_fleet_two_replicas(smoke):
    smoke.phase_fleet(n_replicas=2, n_requests=4)


@pytest.mark.parametrize("argv", [[], ["--four-chip"]],
                         ids=["one_chip", "four_chip"])
def test_main_fails_without_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
