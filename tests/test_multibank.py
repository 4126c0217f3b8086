"""Multi-bank sharded execution layer: digital-merge correctness vs
per-bank reference runs (bit-for-bit), fused single-dispatch execution
vs the per-bank loop oracle (host and pallas inners, dispatch counts),
n_banks=1 parity, ragged row counts, amortized cost model, pallas
matmat kernel, and the device-mesh (shard_map) fan-out — matvec and
matmat."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _parity import assert_bitwise_parity, assert_outs_equal
from repro import dima
from repro.core import energy as en
from repro.core import noise as noise_mod
from repro.core.params import DimaParams

P = DimaParams()
rng = np.random.default_rng(0)
D = jnp.asarray(rng.integers(0, 256, (200, 256)))
Q = jnp.asarray(rng.integers(0, 256, (256,)))
QS = jnp.asarray(rng.integers(0, 256, (3, 256)))
CHIP = noise_mod.sample_chip(jax.random.PRNGKey(3), P)
KEY = jax.random.PRNGKey(9)


# ---------------------------------------------------------------------------
# digital merge == per-bank inner runs, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["dp", "md"])
def test_matvec_is_digital_merge_of_bank_runs(mode):
    """The load-bearing contract: a multibank matvec IS the concatenation
    of per-bank reference runs with fold_in(key, bank) keys — codes and
    volts bitwise identical, cycle/conversion totals bank-invariant."""
    mb = dima.get_backend("multibank", P, CHIP, n_banks=4)
    ref = dima.get_backend("reference", P, CHIP)
    out = mb.matvec(D, Q, mode=mode, key=KEY)
    parts = [ref.matvec(D[a:z], Q, mode=mode,
                        key=jax.random.fold_in(KEY, b))
             for b, (a, z) in enumerate(mb.bank_slices(D.shape[0]))]
    merged = (np.concatenate([np.asarray(o.code) for o in parts]),
              np.concatenate([np.asarray(o.volts) for o in parts]))
    assert_outs_equal(out, merged, label="digital merge")
    unbanked = ref.matvec(D, Q, mode=mode)
    assert out.n_cycles == unbanked.n_cycles
    assert out.n_conversions == unbanked.n_conversions


def test_acceptance_4096x256():
    """The ISSUE's acceptance shape: 4096×256 through 32 banks matches
    the digital merge of per-bank reference runs bit-for-bit, and the
    cost is within 2% of the paper's 231.2 pJ multi-bank MF row."""
    big = jnp.asarray(rng.integers(0, 256, (4096, 256)))
    mb = dima.get_backend("multibank", P)
    assert mb.n_banks == 32
    ref = dima.get_backend("reference", P)
    out = mb.matvec(big, Q, key=KEY)
    merged = np.concatenate(
        [np.asarray(ref.matvec(big[a:z], Q,
                               key=jax.random.fold_in(KEY, b)).code)
         for b, (a, z) in enumerate(mb.bank_slices(4096))])
    np.testing.assert_array_equal(np.asarray(out.code), merged)
    cost = mb.decision_cost(256)
    assert abs(cost.energy_pj - en.PAPER_TABLE["mf"][1]) \
        / en.PAPER_TABLE["mf"][1] < 0.02


def test_nbanks1_parity_with_reference():
    """One bank = the unbanked substrate: zero-noise results identical;
    with noise, bank 0's stream is fold_in(key, 0) by construction."""
    mb = dima.get_backend("multibank", P, CHIP, n_banks=1)
    ref = dima.get_backend("reference", P, CHIP)
    assert_bitwise_parity("matvec", ref, mb, D, Q, counts=True)
    n = mb.matvec(D, Q, key=KEY)
    r = ref.matvec(D, Q, key=jax.random.fold_in(KEY, 0))
    assert_outs_equal(n, r, counts=False, label="fold_in(key, 0) stream")


@pytest.mark.parametrize("m,n_banks", [(50, 8), (5, 8), (200, 7)])
def test_ragged_row_counts(m, n_banks):
    """Rows not divisible by bank count: last bank ragged, trailing banks
    empty — output still (m,) and still the exact digital merge."""
    mb = dima.get_backend("multibank", P, n_banks=n_banks)
    ref = dima.get_backend("reference", P)
    slices = mb.bank_slices(m)
    assert slices[0][0] == 0 and slices[-1][1] == m
    assert all(a2 == z1 for (_, z1), (a2, _) in zip(slices, slices[1:]))
    out = mb.matvec(D[:m], Q, key=KEY)
    assert out.code.shape == (m,) and out.n_conversions == m
    merged = np.concatenate(
        [np.asarray(ref.matvec(D[a:z], Q,
                               key=jax.random.fold_in(KEY, b)).code)
         for b, (a, z) in enumerate(slices)])
    np.testing.assert_array_equal(np.asarray(out.code), merged)


def test_matmat_merge_and_pallas_inner():
    """matmat shards rows and merges codes on axis 1; the pallas inner
    runs each bank as one query-batched kernel launch and agrees with the
    reference inner exactly at zero noise."""
    for inner in ("reference", "pallas"):
        mb = dima.get_backend("multibank", P, inner=inner, n_banks=4)
        out = mb.matmat(D, QS)
        assert out.code.shape == (3, 200)
        ref = dima.get_backend("reference", P).matmat(D, QS)
        np.testing.assert_array_equal(np.asarray(out.code),
                                      np.asarray(ref.code))
    noisy = dima.get_backend("multibank", P, inner="pallas",
                             n_banks=4).matmat(D, QS, key=KEY)
    assert noisy.code.shape == (3, 200)


def test_dot_delegates_and_apps_run():
    """Single ops delegate to the inner substrate (one op = one bank), so
    the calibration layer and the broadcast-layout apps work unchanged."""
    mb = dima.get_backend("multibank", P, CHIP, n_banks=4)
    ref = dima.get_backend("reference", P, CHIP)
    a = mb.dot(D[0], Q, key=KEY)
    b = ref.dot(D[0], Q, key=KEY)
    np.testing.assert_array_equal(np.asarray(a.code), np.asarray(b.code))
    assert mb.ideal().chip is None and mb.ideal().n_banks == 4
    from repro.core.applications import run_tm
    r = run_tm(P, CHIP, KEY, backend="multibank")
    assert abs(r.acc_dima - r.acc_digital) <= 0.02 + 1e-9


# ---------------------------------------------------------------------------
# fused single-dispatch execution vs the per-bank loop oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["dp", "md"])
@pytest.mark.parametrize("m,n_banks", [(200, 4),   # even split
                                       (50, 8),    # ragged last bank
                                       (200, 1)])  # degenerate single bank
def test_fused_matches_loop_bitwise(mode, m, n_banks):
    """The fused path (bank axis vmapped inside one jit dispatch, ragged
    remainder a second branch of the same computation) IS the per-bank
    loop: codes AND volts bitwise identical for matvec and matmat, with
    and without noise, cycle/conversion totals unchanged."""
    fused = dima.get_backend("multibank", P, CHIP, n_banks=n_banks)
    loop = dima.get_backend("multibank", P, CHIP, n_banks=n_banks,
                            fused=False)
    for key in (None, KEY):
        assert_bitwise_parity("matvec", loop, fused, D[:m], Q, mode=mode,
                              key=key, counts=True)
        am = fused.matmat(D[:m], QS, mode=mode, key=key)
        assert am.code.shape == (3, m)
        assert_bitwise_parity("matmat", loop, fused, D[:m], QS, mode=mode,
                              key=key, counts=True)


def _pallas_bank_loop(m, n_banks, kind, q, mode, key, v_range):
    """Per-bank Pallas launches, bank ``b`` under its own window row
    ``v_range[b]``: the oracle for a per-bank (n_banks, 2) window."""
    inner = dima.get_backend("pallas", P, CHIP)
    mb = dima.get_backend("multibank", P, CHIP, n_banks=n_banks)
    outs = [getattr(inner, kind)(D[a:z], q, mode=mode,
                                 key=jax.random.fold_in(key, b),
                                 v_range=tuple(v_range[b]))
            for b, (a, z) in enumerate(mb.bank_slices(m))]
    return mb._merge(outs, axis=0 if kind == "matvec" else 1)


# operand forms the fused step takes inside its jit: v_range None, a
# shared (lo, hi) window or a per-bank (n_banks, 2) array; trim None or
# a numpy triple with the benchmark's numpy uint32 key pair
FUSED_PALLAS_FORMS = [
    pytest.param(200, 4, "plain", id="200-4"),
    pytest.param(50, 8, "plain", id="50-8"),
    pytest.param(200, 4, "window", id="200-4-window"),
    pytest.param(50, 8, "window", id="50-8-window"),
    pytest.param(200, 4, "banks", id="200-4-banks"),
    pytest.param(200, 4, "trim", id="200-4-trim"),
    pytest.param(50, 8, "trim", id="50-8-trim"),
]


@pytest.mark.parametrize("mode", ["dp", "md"])
@pytest.mark.parametrize("m,n_banks,form", FUSED_PALLAS_FORMS)
def test_fused_pallas_inner_matches_loop(mode, m, n_banks, form):
    """Pallas inner, interpret mode: the fused (n_banks, B, rows/128)
    bank-grid launch matches the per-bank kernel-launch loop — codes
    bitwise (full banks AND the separately-launched ragged remainder);
    volts to 1 ulp (XLA reassociation across the different launch
    shapes, same envelope as the jitted-reference precedent); trimmed
    scores to the fused epilogue's float tolerance."""
    fused = dima.get_backend("multibank", P, CHIP, inner="pallas",
                             n_banks=n_banks)
    loop = dima.get_backend("multibank", P, CHIP, inner="pallas",
                            n_banks=n_banks, fused=False)
    full = (255.0 * 255.0 * dima.dp_gain(P) if mode == "dp"
            else 255.0 * dima.md_gain(P))
    if form == "plain":
        for key in (None, KEY):
            assert_bitwise_parity("matvec", loop, fused, D[:m], Q,
                                  mode=mode, key=key, volts_atol=1e-7,
                                  counts=False)
            am = fused.matmat(D[:m], QS, mode=mode, key=key)
            assert am.code.shape == (3, m)
            assert_bitwise_parity("matmat", loop, fused, D[:m], QS,
                                  mode=mode, key=key, volts_atol=1e-7,
                                  counts=False)
        return
    kw = dict(mode=mode, key=KEY)
    if form == "window":
        kw["v_range"] = (0.1 * full, 0.6 * full)
    elif form == "banks":
        kw["v_range"] = np.stack([np.linspace(0.0, 0.2, n_banks) * full,
                                  np.linspace(0.5, 0.8, n_banks) * full],
                                 1).astype(np.float32)
    else:
        kw.update(key=np.asarray(KEY, np.uint32),
                  trim=np.asarray([0.97, -0.4, 12.5], np.float32))
    for kind, q in (("matvec", Q), ("matmat", QS)):
        got = getattr(fused, kind)(D[:m], q, **kw)
        if form == "banks":
            want = _pallas_bank_loop(m, n_banks, kind, q, mode, KEY,
                                     kw["v_range"])
        else:
            want = getattr(loop, kind)(D[:m], q, **kw)
        assert_outs_equal(want, got, volts_atol=1e-7, counts=False,
                          label=f"{kind}/{form}")
        if form == "trim":
            assert got.trimmed.shape == got.code.shape
            np.testing.assert_allclose(np.asarray(got.trimmed),
                                       np.asarray(want.trimmed),
                                       rtol=2e-6, atol=1e-2)


def test_fused_dispatch_counts():
    """The load-bearing perf contract (also guarded by benchmarks/run.py
    --smoke in CI): a fused multibank matvec/matmat is ONE compiled-
    computation launch — even with a ragged last bank on the host path,
    where the remainder is a branch of the same jitted computation — vs
    one launch per occupied bank on the loop oracle.  The fused Pallas
    path is one launch per even split and two when ragged (the
    remainder's noise shapes differ, so it launches separately)."""
    mb = dima.get_backend("multibank", P, n_banks=8)
    loop = dima.get_backend("multibank", P, n_banks=8, fused=False)
    for be, dat, expect in [(mb, D[:160], 1), (mb, D[:50], 1),
                            (loop, D[:160], 8), (loop, D[:50], 8)]:
        be.matvec(dat, Q, key=KEY)                       # warm up
        with dima.count_dispatches() as c:
            be.matvec(dat, Q, key=KEY)
        assert c.n == expect, (be.fused, dat.shape, c.n)
    mb.matmat(D[:160], QS, key=KEY)
    with dima.count_dispatches() as c:
        mb.matmat(D[:160], QS, key=KEY)
    assert c.n == 1
    pal = dima.get_backend("multibank", P, inner="pallas", n_banks=8)
    for dat, expect in [(D[:160], 1), (D[:50], 2)]:
        pal.matvec(dat, Q, key=KEY)
        with dima.count_dispatches() as c:
            pal.matvec(dat, Q, key=KEY)
        assert c.n == expect


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_amortized_cost_model():
    mb = dima.get_backend("multibank", P)
    single = en.dima_decision(P, 256)
    multi = mb.decision_cost(256)
    assert multi.energy_pj < single.energy_pj
    # the fixed split is exactly what the merge path charges per bank
    assert en.bank_fixed_split(P) == pytest.approx(P.e_fixed_conv_pj / 32)
    assert multi.energy_pj == pytest.approx(
        single.energy_pj - P.e_fixed_conv_pj + en.bank_fixed_split(P))
    # a non-default bank count amortizes by its own count
    mb8 = dima.get_backend("multibank", P, n_banks=8)
    assert mb8.decision_cost(256).energy_pj == pytest.approx(
        single.energy_pj - P.e_fixed_conv_pj + P.e_fixed_conv_pj / 8)
    assert mb8.bank_fixed_pj == pytest.approx(P.e_fixed_conv_pj / 8)


def test_weights_energy_per_token_switches_on_backend():
    n_active = 1_000_000
    pj_single, _ = dima.weights_energy_per_token(
        n_active, dima.get_backend("reference", P))
    pj_multi, banks = dima.weights_energy_per_token(
        n_active, dima.get_backend("multibank", P))
    pj_forced, _ = dima.weights_energy_per_token(
        n_active, dima.get_backend("reference", P), multi_bank=True)
    assert pj_multi < pj_single            # amortized CTRL
    assert pj_forced == pytest.approx(pj_multi)   # explicit what-if


# ---------------------------------------------------------------------------
# device-mesh fan-out
# ---------------------------------------------------------------------------

def test_mesh_path_matches_host_path_single_device():
    """bank_mesh degenerates to one shard on one device but still runs
    the shard_map code path — results must match the host fan-out
    bitwise."""
    from repro.distributed.sharding import bank_mesh
    mesh = bank_mesh(8)
    mb_mesh = dima.get_backend("multibank", P, CHIP, n_banks=8, mesh=mesh)
    mb_host = dima.get_backend("multibank", P, CHIP, n_banks=8)
    a = mb_mesh.matvec(D[:160], Q, key=KEY)
    b = mb_host.matvec(D[:160], Q, key=KEY)
    np.testing.assert_array_equal(np.asarray(a.code), np.asarray(b.code))
    np.testing.assert_allclose(np.asarray(a.volts), np.asarray(b.volts),
                               atol=1e-7)


def test_mesh_matmat_matches_host_path_single_device():
    """matmat over the mesh (shard_map over the banks axis, one launch)
    == the host fused path, bitwise on codes — both run the same
    per-bank core, so the digital merge is identical in row order."""
    from repro.distributed.sharding import bank_mesh
    mesh = bank_mesh(8)
    mb_mesh = dima.get_backend("multibank", P, CHIP, n_banks=8, mesh=mesh)
    mb_host = dima.get_backend("multibank", P, CHIP, n_banks=8)
    for key in (None, KEY):
        a = mb_mesh.matmat(D[:160], QS, key=key)
        b = mb_host.matmat(D[:160], QS, key=key)
        assert a.code.shape == (3, 160)
        np.testing.assert_array_equal(np.asarray(a.code),
                                      np.asarray(b.code))
        np.testing.assert_allclose(np.asarray(a.volts), np.asarray(b.volts),
                                   atol=1e-7)
        assert (a.n_cycles, a.n_conversions) == (b.n_cycles,
                                                 b.n_conversions)


def test_mesh_path_rejects_ragged():
    from repro.distributed.sharding import bank_mesh
    mb = dima.get_backend("multibank", P, n_banks=8, mesh=bank_mesh(8))
    with pytest.raises(ValueError, match="ragged"):
        mb.matvec(D[:50], Q)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="single-device runtime; multi-device fan-out "
                           "covered by the subprocess smoke test")
def test_mesh_path_multi_device():
    from repro.distributed.sharding import bank_mesh
    mesh = bank_mesh(8)
    assert mesh.shape["banks"] > 1
    mb_mesh = dima.get_backend("multibank", P, n_banks=8, mesh=mesh)
    mb_host = dima.get_backend("multibank", P, n_banks=8)
    a = mb_mesh.matvec(D[:160], Q, key=KEY)
    b = mb_host.matvec(D[:160], Q, key=KEY)
    np.testing.assert_array_equal(np.asarray(a.code), np.asarray(b.code))


@pytest.mark.slow
def test_mesh_smoke_subprocess_four_devices():
    """Real multi-device shard_map fan-out: re-launch with 4 forced host
    devices and assert mesh == host bitwise."""
    prog = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        from repro import dima
        from repro.distributed.sharding import bank_mesh
        assert len(jax.devices()) == 4
        P = dima.DimaParams()
        rng = np.random.default_rng(0)
        D = jnp.asarray(rng.integers(0, 256, (256, 256)))
        Q = jnp.asarray(rng.integers(0, 256, (256,)))
        KEY = jax.random.PRNGKey(9)
        mesh = bank_mesh(8)
        assert mesh.shape["banks"] == 4
        a = dima.get_backend("multibank", P, n_banks=8,
                             mesh=mesh).matvec(D, Q, key=KEY)
        b = dima.get_backend("multibank", P,
                             n_banks=8).matvec(D, Q, key=KEY)
        np.testing.assert_array_equal(np.asarray(a.code),
                                      np.asarray(b.code))
        print("MESH_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_OK" in out.stdout


@pytest.mark.slow
def test_mesh_matmat_smoke_subprocess_four_devices():
    """Real multi-device shard_map matmat: re-launch with 4 forced host
    devices and assert mesh matmat == host matmat bitwise (the matmat
    sibling of the matvec subprocess smoke above)."""
    prog = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        from repro import dima
        from repro.distributed.sharding import bank_mesh
        assert len(jax.devices()) == 4
        P = dima.DimaParams()
        rng = np.random.default_rng(0)
        D = jnp.asarray(rng.integers(0, 256, (256, 256)))
        QS = jnp.asarray(rng.integers(0, 256, (3, 256)))
        KEY = jax.random.PRNGKey(9)
        mesh = bank_mesh(8)
        assert mesh.shape["banks"] == 4
        a = dima.get_backend("multibank", P, n_banks=8,
                             mesh=mesh).matmat(D, QS, key=KEY)
        b = dima.get_backend("multibank", P,
                             n_banks=8).matmat(D, QS, key=KEY)
        assert a.code.shape == (3, 256)
        np.testing.assert_array_equal(np.asarray(a.code),
                                      np.asarray(b.code))
        print("MESH_MATMAT_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_MATMAT_OK" in out.stdout


# ---------------------------------------------------------------------------
# registry / dispatch satellites
# ---------------------------------------------------------------------------

def test_get_backend_typo_raises_keyerror_with_names():
    with pytest.raises(KeyError, match="registered backends"):
        dima.get_backend("multibanc")
    with pytest.raises(KeyError, match="multibank"):
        dima.get_backend("multibanc")          # close-match hint


def test_pallas_rejects_unimplemented_mode():
    pal = dima.get_backend("pallas", P)
    for op in (pal.matvec, pal.matmat):
        with pytest.raises(ValueError, match="unknown mode"):
            op(D, QS if op is pal.matmat else Q, mode="xor")
    # a hypothetical future MODES entry must not silently fall through
    orig = dima.MODES
    try:
        import repro.core.api as api_mod
        api_mod.MODES = ("dp", "md", "xnor")
        with pytest.raises(ValueError, match="reference"):
            pal.matvec(D, Q, mode="xnor")
    finally:
        api_mod.MODES = orig


def test_auto_min_rows_from_measured_crossover(tmp_path, monkeypatch):
    bench = tmp_path / "BENCH_dima_api.json"
    bench.write_text(json.dumps({"auto_crossover_rows": 64}))
    monkeypatch.setenv("DIMA_BENCH_JSON", str(bench))
    auto = dima.get_backend("auto", P)
    assert auto.min_rows == 64
    assert type(auto.pick(D[:64], Q)).name == "pallas"
    # absent / null crossover falls back to the static default
    bench.write_text(json.dumps({"auto_crossover_rows": None}))
    assert dima.get_backend("auto", P).min_rows == 128
    monkeypatch.setenv("DIMA_BENCH_JSON", str(tmp_path / "missing.json"))
    assert dima.get_backend("auto", P).min_rows == 128
    # "never" (measured: pallas loses everywhere) is NOT the fallback —
    # it keeps auto off the pallas path entirely
    monkeypatch.setenv("DIMA_BENCH_JSON", str(bench))
    bench.write_text(json.dumps({"auto_crossover_rows": "never"}))
    never = dima.get_backend("auto", P)
    assert never.min_rows > 10 ** 9
    assert type(never.pick(D, Q)).name == "reference"
    # explicit min_rows always wins
    assert dima.get_backend("auto", P, min_rows=7).min_rows == 7


def test_auto_min_rows_platform_keyed_crossover(tmp_path, monkeypatch):
    """The platform-keyed ``crossover`` section: AutoBackend reads the
    entry matching ``jax.default_backend()``; other platforms' rows are
    ignored; the nested section takes precedence over the legacy flat
    tags; a section without this platform falls back to the flat pair."""
    plat = jax.default_backend()
    other = "tpu" if plat != "tpu" else "gpu"
    bench = tmp_path / "BENCH_dima_api.json"
    monkeypatch.setenv("DIMA_BENCH_JSON", str(bench))
    bench.write_text(json.dumps(
        {"crossover": {plat: {"rows": 32}, other: {"rows": 999}}}))
    assert dima.get_backend("auto", P).min_rows == 32
    # nested beats legacy flat
    bench.write_text(json.dumps(
        {"crossover": {plat: {"rows": 48}},
         "auto_crossover_rows": 64, "auto_crossover_platform": plat}))
    assert dima.get_backend("auto", P).min_rows == 48
    # only the OTHER platform measured -> static default, not its value
    bench.write_text(json.dumps({"crossover": {other: {"rows": 16}}}))
    assert dima.get_backend("auto", P).min_rows == 128
    # "never" in the nested layout keeps auto off pallas entirely
    bench.write_text(json.dumps({"crossover": {plat: {"rows": "never"}}}))
    assert dima.get_backend("auto", P).min_rows > 10 ** 9


def test_multibank_rejects_nested_inner():
    with pytest.raises(ValueError, match="single-bank"):
        dima.get_backend("multibank", P,
                         inner=dima.get_backend("multibank", P))


def test_multibank_rejects_bad_bank_count_and_mesh_inner():
    with pytest.raises(ValueError, match="n_banks"):
        dima.get_backend("multibank", P, n_banks=0)
    # the mesh path runs the reference pipeline or the banked Pallas
    # kernels per shard: any other inner must fail at construction, not
    # silently diverge from the host path
    from repro.distributed.sharding import bank_mesh
    mb = dima.get_backend("multibank", P, inner="pallas", n_banks=8,
                          mesh=bank_mesh(8))
    assert mb.inner.name == "pallas"
    with pytest.raises(ValueError, match="reference pipeline"):
        dima.get_backend("multibank", P, inner="digital", n_banks=8,
                         mesh=bank_mesh(8))


def test_mesh_pallas_inner_matches_host_pallas_fused():
    """The kernel-only device path: a pallas-inner mesh matvec/matmat
    runs the banked Pallas kernels per shard and must reproduce the host
    fused-pallas path — ADC codes BITWISE; volts and the fused trimmed
    output to the float-assembly tolerance (interpret-mode Pallas
    compiles through XLA, which may reassociate the shared voltage chain
    by ~1 ulp when the trim output is present — same policy as the
    pallas~reference row of the standing parity matrix)."""
    from repro.distributed.sharding import bank_mesh
    mesh = bank_mesh(8)
    mb_mesh = dima.get_backend("multibank", P, CHIP, n_banks=8,
                               inner="pallas", mesh=mesh)
    mb_host = dima.get_backend("multibank", P, CHIP, n_banks=8,
                               inner="pallas")
    trim = np.asarray([0.9, -0.3, 2.0], np.float32)
    for key in (None, KEY):
        a = mb_mesh.matvec(D[:160], Q, key=key, trim=trim)
        b = mb_host.matvec(D[:160], Q, key=key, trim=trim)
        np.testing.assert_array_equal(np.asarray(a.code),
                                      np.asarray(b.code))
        np.testing.assert_allclose(np.asarray(a.volts),
                                   np.asarray(b.volts), atol=1e-7)
        np.testing.assert_allclose(np.asarray(a.trimmed),
                                   np.asarray(b.trimmed), rtol=2e-6,
                                   atol=1e-2)
        am = mb_mesh.matmat(D[:160], QS, key=key, trim=trim)
        bm = mb_host.matmat(D[:160], QS, key=key, trim=trim)
        assert am.code.shape == (3, 160)
        np.testing.assert_array_equal(np.asarray(am.code),
                                      np.asarray(bm.code))
        np.testing.assert_allclose(np.asarray(am.trimmed),
                                   np.asarray(bm.trimmed), rtol=2e-6,
                                   atol=1e-2)


def test_mesh_pallas_inner_reference_oracle():
    """The mesh-pallas path against the independent oracle: per-bank
    *reference* runs — codes bitwise at zero noise (the cross-substrate
    regime the standing parity matrix pins; a noisy draw sits at ADC
    rounding boundaries where the kernel's float assembly may flip a
    code by 1 LSB vs the jnp pipeline).  This ties the kernel-only
    device path to the digital-merge contract, not just to
    pallas-vs-pallas self-consistency."""
    from repro.distributed.sharding import bank_mesh
    mb = dima.get_backend("multibank", P, CHIP, n_banks=8,
                          inner="pallas", mesh=bank_mesh(8))
    ref = dima.get_backend("reference", P, CHIP)
    out = mb.matvec(D[:160], Q)
    merged = np.concatenate(
        [np.asarray(ref.matvec(D[a:z], Q).code)
         for (a, z) in mb.bank_slices(160)])
    np.testing.assert_array_equal(np.asarray(out.code), merged)


def test_measured_min_rows_is_cwd_independent(tmp_path, monkeypatch):
    """AutoBackend dispatch must not change with the launch directory:
    the default bench path anchors at the repo root, not the CWD."""
    monkeypatch.delenv("DIMA_BENCH_JSON", raising=False)
    here = dima.measured_min_rows()
    monkeypatch.chdir(tmp_path)
    assert dima.measured_min_rows() == here
