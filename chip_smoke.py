"""Smoke run of the system's main paths on a TPU, through the entry points
a user calls.

    python chip_smoke.py               # one chip: kernels, apps, serve
    python chip_smoke.py --four-chip   # four chips: replica fleet, mesh

One chip, phases in order in this one process:

* ``kernels`` — the flagship 4096×256 DP and MD ops over 32 banks,
  matvec and matmat (B=8), with and without the fused trim, through
  ``get_backend("pallas")`` and ``MultiBankBackend(inner="pallas")``:
  native lowering (``tpu_custom_call`` in the compiled program), codes
  bitwise equal to ``get_backend("reference")`` at ``key=None``, and
  the fused trimmed 32-bank matvec counted as one dispatch;
* ``apps`` — ``applications.run_all(backend="pallas")`` on the seed
  chip/noise keys, accuracies within 1 point of the seed's;
* ``serve`` — ``repro.launch.serve`` on gemma3-1b at its published
  widths: four requests through the DIMA-quantized multibank engine,
  then the same through the analog-LM chain.

``--four-chip`` runs only what exists across chips: a 4-replica fleet
(one replica process per chip) whose tokens must equal the sequential
dense oracle, then the multibank matvec/matmat over a 4-device
``bank_mesh`` whose codes must equal the one-device fused path.

Every phase prints its timings, checks and device memory.  Any failure
exits non-zero; so does a missing TPU (there is no CPU fallback) and a
``DIMA_PALLAS_INTERPRET`` that would force interpret mode on the chip.
The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: seed-parity accuracies of ``run_all()`` (chip_key=7, noise_key=11):
#: app -> (analog, digital)
SEED_ACC = {"svm": (0.94, 0.93), "mf": (1.0, 1.0), "tm": (1.0, 1.0),
            "knn": (0.89, 0.89)}
ACC_TOL = 0.01
#: an arbitrary affine trim: exercises the fused calibration epilogue
TRIM = (0.97, 0.01, 3.0)


class SmokeFailure(Exception):
    """A phase check did not hold."""


def _check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def _log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def _memory(phase):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    _log(phase, "device memory: " + ", ".join(
        f"{k}={stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit") if k in stats))


def _timed(fn, n=5):
    """(result, first-call s, median steady-state s) of ``fn``, each call
    ended by ``block_until_ready`` on its outputs."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return out, first, float(np.median(ts))


def require_tpu(n_chips):
    """The device contract: JAX sees ``n_chips`` TPU chips and the
    kernels will lower natively on them."""
    import jax

    from repro.kernels._interpret import ENV_VAR, resolve_interpret
    devs = jax.devices()
    _check(devs[0].platform == "tpu",
           f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    _check(len(devs) >= n_chips,
           f"need {n_chips} TPU chips, JAX sees {len(devs)}")
    _check(resolve_interpret(None) is False,
           f"${ENV_VAR}={os.environ.get(ENV_VAR)!r} forces Pallas interpret "
           "mode on a TPU")
    return devs


def _data(m, b, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, 256, (m, 256)), jnp.uint8),
            jnp.asarray(rng.integers(0, 256, (b, 256)), jnp.uint8))


def phase_kernels(m=4096, n_banks=32, b=8, native=True):
    """Flagship DIMA ops on the pallas and multibank-pallas backends
    against the reference backend, at ``key=None``."""
    import jax

    from repro import dima
    from repro.core.params import DimaParams
    from repro.kernels._interpret import resolve_interpret
    p = DimaParams()
    chip = dima.sample_chip(jax.random.PRNGKey(7), p)
    d, qs = _data(m, b)
    ref = dima.get_backend("reference", p, chip)
    pallas = dima.get_backend("pallas", p, chip)
    multi = dima.get_backend("multibank", p, chip, inner="pallas",
                             n_banks=n_banks)
    if native:
        _check(resolve_interpret(pallas.interpret) is False,
               "pallas backend resolved interpret=True")
    _log("kernels", f"{m}x256 rows, {n_banks} banks, matmat B={b}, "
         f"interpret={resolve_interpret(pallas.interpret)}")
    bad = []
    for mode in ("dp", "md"):
        for op in ("matvec", "matmat"):
            q = qs[0] if op == "matvec" else qs
            want = getattr(ref, op)(d, q, mode=mode, trim=TRIM)
            for name, be in (("pallas", pallas), ("multibank", multi)):
                for trim in (None, TRIM):
                    def call(dd=d, qq=q, be=be, op=op, mode=mode, trim=trim):
                        return getattr(be, op)(dd, qq, mode=mode, trim=trim)
                    out, first, steady = _timed(call)
                    tag = f"{name} {mode} {op}{' trim' if trim else ''}"
                    n_bad = int(np.sum(np.asarray(out.code)
                                       != np.asarray(want.code)))
                    msg = (f"{tag}: {n_bad} of {out.code.size} codes differ "
                           "from the reference backend")
                    if trim is not None:
                        err = np.abs(np.asarray(out.trimmed)
                                     - np.asarray(want.trimmed))
                        tol = 1e-2 + 2e-6 * np.abs(np.asarray(want.trimmed))
                        n_bad += int(np.sum(err > tol))
                        msg += f"; trimmed max |err| {err.max():.3g}"
                    if native:
                        hlo = jax.jit(lambda dd, qq: call(dd, qq).code
                                      ).lower(d, q).compile().as_text()
                        if "tpu_custom_call" not in hlo:
                            n_bad += 1
                            msg += "; no tpu_custom_call in the program"
                    _log("kernels", f"{msg}, first {first * 1e3:.3f} ms, "
                         f"steady {steady * 1e3:.3f} ms")
                    if n_bad:
                        bad.append(tag)
    _check(not bad, f"kernel configurations off the reference: {bad}")
    q = qs[0]
    jax.block_until_ready(multi.matvec(d, q, trim=TRIM))
    with dima.count_dispatches() as c:
        jax.block_until_ready(multi.matvec(d, q, trim=TRIM))
    _check(c.n == 1, f"fused trimmed {n_banks}-bank matvec issued {c.n} "
           "dispatches, expected 1")
    # traced on its first call: the inner launch returns tracers there
    traced = jax.jit(lambda dd, qq: multi.matvec(dd, qq, trim=TRIM).code)
    with dima.count_dispatches() as c_traced:
        jax.block_until_ready(traced(d, q))
    _check(c_traced.n == 0, f"a launch traced into an enclosing jit was "
           f"counted ({c_traced.n})")
    _log("kernels", f"fused trimmed {n_banks}-bank matvec: {c.n} dispatch; "
         f"traced inside an enclosing jit: {c_traced.n}")


def phase_apps(backend="pallas", apps=None):
    """The paper's four applications on ``backend`` against the seed's
    accuracies."""
    from repro.core import applications
    t0 = time.perf_counter()
    res = applications.run_all(backend=backend, apps=apps)
    _log("apps", f"run_all(backend={backend!r}) in "
         f"{time.perf_counter() - t0:.1f} s")
    for name, r in res.items():
        want_a, want_d = SEED_ACC[name]
        _log("apps", f"{name}: analog {r.acc_dima:.2f} (seed {want_a}), "
             f"digital {r.acc_digital:.2f} (seed {want_d}), "
             f"{r.n_queries} queries on {backend}")
        _check(abs(r.acc_dima - want_a) <= ACC_TOL + 1e-9
               and abs(r.acc_digital - want_d) <= ACC_TOL + 1e-9,
               f"{name} accuracy off the seed by more than {ACC_TOL}")


def phase_serve(arch="gemma3-1b", batch=4, prompt_len=32, gen=16,
                analog=False, reduced=False):
    """Four requests through ``repro.launch.serve`` on ``arch``."""
    from repro.configs import get_arch
    from repro.launch import serve
    argv = ["--arch", arch, "--batch", str(batch), "--prompt-len",
            str(prompt_len), "--gen", str(gen), "--backend", "multibank"]
    argv += ["--analog-lm"] if analog else ["--quant", "dima"]
    argv += ["--reduced"] if reduced else []
    tag = "serve analog-lm" if analog else "serve dima"
    _log(tag, "python -m repro.launch.serve " + " ".join(argv))
    t0 = time.perf_counter()
    out = np.asarray(serve.main(argv))
    _log(tag, f"{batch} requests answered in "
         f"{time.perf_counter() - t0:.1f} s incl. compile, shape {out.shape}")
    vocab = get_arch(arch).vocab_size
    _check(out.shape == (batch, gen), f"{tag}: output shape {out.shape}, "
           f"expected {(batch, gen)}")
    _check(((out >= 0) & (out < vocab)).all(),
           f"{tag}: token ids outside [0, {vocab})")


def phase_fleet(n_replicas=4, n_requests=12, timeout_s=300):
    """A replica fleet, one process per chip, against the sequential
    dense oracle.  Greedy tokens are paging- and batching-invariant only
    up to float rounding, so both sides run f32 matmuls at full
    precision (the replicas inherit the environment)."""
    import jax

    from repro.launch.replicas import make_shared_trace, run_fleet
    env_prev = os.environ.get("JAX_DEFAULT_MATMUL_PRECISION")
    prec_prev = jax.config.jax_default_matmul_precision
    os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
    jax.config.update("jax_default_matmul_precision", "highest")

    def stalled(signum, frame):
        raise TimeoutError(f"fleet phase exceeded {timeout_s} s")

    sig_prev = signal.signal(signal.SIGALRM, stalled)
    signal.alarm(timeout_s)
    t0 = time.perf_counter()
    try:
        rec = run_fleet(n_replicas=n_replicas, rate_rps=10.0,
                        n_requests=n_requests, max_batch=4, max_len=64,
                        bucket=32, block_size=16, check_tokens=True,
                        trace=make_shared_trace(n_requests, seed=0,
                                                max_news=(2, 8)))
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, sig_prev)
        jax.config.update("jax_default_matmul_precision", prec_prev)
        if env_prev is None:
            os.environ.pop("JAX_DEFAULT_MATMUL_PRECISION")
        else:
            os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = env_prev
    _check(rec.get("token_identity") == "ok", "fleet tokens unchecked")
    _log("fleet", f"{n_replicas} replicas on {rec['platform']} "
         f"({rec['device_kind']}): {rec['requests']} requests, "
         f"{rec['tokens']} tokens == sequential dense oracle; "
         f"{rec['fleet_tokens_per_s']} tok/s, p50 {rec['latency_p50_s']} s, "
         f"p99 {rec['latency_p99_s']} s, run {time.perf_counter() - t0:.1f} s")
    for name, r in rec["per_replica"].items():
        _log("fleet", f"{name}: {r['requests']} requests, "
             f"utilization {r['utilization']}")


def phase_mesh(devices, m=4096, n_banks=32, b=8):
    """Multibank pallas over a ``bank_mesh`` of ``devices`` against the
    fused host path on one device: codes bitwise equal."""
    import jax

    from repro import dima
    from repro.core.params import DimaParams
    from repro.distributed.sharding import bank_mesh
    p = DimaParams()
    chip = dima.sample_chip(jax.random.PRNGKey(7), p)
    mesh = bank_mesh(n_banks, devices)
    _check(mesh.shape["banks"] == len(devices),
           f"bank mesh spans {mesh.shape['banks']} of {len(devices)} devices")
    d, qs = _data(m, b)
    host = dima.get_backend("multibank", p, chip, inner="pallas",
                            n_banks=n_banks)
    meshed = dima.get_backend("multibank", p, chip, inner="pallas",
                              n_banks=n_banks, mesh=mesh)
    _log("mesh", f"{n_banks} banks over {len(devices)} devices "
         f"({n_banks // len(devices)} per device), {m}x256 rows")
    for mode in ("dp", "md"):
        for op in ("matvec", "matmat"):
            q = qs[0] if op == "matvec" else qs
            for trim in (None, TRIM):
                want = getattr(host, op)(d, q, mode=mode, trim=trim)
                out, first, steady = _timed(
                    lambda: getattr(meshed, op)(d, q, mode=mode, trim=trim))
                tag = f"{mode} {op}{' trim' if trim else ''}"
                n_bad = int(np.sum(np.asarray(out.code)
                                   != np.asarray(want.code)))
                _check(n_bad == 0, f"mesh {tag}: {n_bad} codes differ from "
                       "the one-device fused path")
                _log("mesh", f"{tag}: codes == host fused path, first "
                     f"{first * 1e3:.3f} ms, steady {steady * 1e3:.3f} ms")


def _run_phase(failed, name, fn, *args, **kw):
    """Run one phase; a failure is reported and recorded, and the later
    phases still run."""
    t0 = time.perf_counter()
    _log(name, "start")
    try:
        fn(*args, **kw)
    except Exception as e:              # noqa: BLE001 — reported, then exit 1
        traceback.print_exc()
        _log(name, f"FAILED after {time.perf_counter() - t0:.1f} s: "
             f"{type(e).__name__}: {e}")
        failed.append(name)
    else:
        _log(name, f"ok in {time.perf_counter() - t0:.1f} s")
    _memory(name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="only the cross-chip paths: a 4-replica fleet "
                         "and the 4-device bank mesh")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.replicas import tpu_fleet, visible_tpu_chips
    failed = []
    try:
        if args.four_chip:
            # the replicas claim the chips first: this process stays off
            # JAX until they have exited
            _check(tpu_fleet(), "no TPU chips visible to this process")
            _check(visible_tpu_chips() >= 4,
                   f"need 4 TPU chips, {visible_tpu_chips()} visible")
            _log("smoke", "compile cache: " + enable_compile_cache())
            _run_phase(failed, "fleet", phase_fleet)
            devs = require_tpu(4)
            _run_phase(failed, "mesh", phase_mesh, devs[:4])
        else:
            _log("smoke", "compile cache: " + enable_compile_cache())
            devs = require_tpu(1)
            _run_phase(failed, "kernels", phase_kernels)
            _run_phase(failed, "apps", phase_apps)
            _run_phase(failed, "serve dima", phase_serve)
            _run_phase(failed, "serve analog-lm", phase_serve, analog=True)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if failed:
        print(f"[smoke] FAILED phases: {failed}", file=sys.stderr, flush=True)
        return 1
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
