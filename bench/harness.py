"""One run of one benchmark cell: set-up, a timed window, a traced
window's reduction, and the comparison that decides ``correct``.

Everything a cell is made of is found by name: the cell and its
configuration in ``BENCHMARK.json``, the configuration's file, the
traffic mix ``bench/traffic/<traffic>.json``, the application
``bench/apps/<app>.py`` the configuration names, and one reader
``bench/metrics/<metric>.py`` per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic mix file's contents
    app: object           # bench/apps/<app>.py
    end_to_end: list      # names of the end-to-end metrics it reports
    per_layer: dict       # metric name -> (entry, reader module)


def _reports(metric: dict, cell: str, spec: dict) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if metric in spec["end_to_end"]:
        return True
    moves = next(m for m in spec["end_to_end"] if m["name"] == metric["moves"])
    return _reports(moves, cell, spec)


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name`` with everything it is made of."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    app = load_module(root / "bench" / "apps" / f"{config['app']}.py",
                      f"bench.apps.{config['app']}")
    e2e = [m["name"] for m in spec["end_to_end"] if _reports(m, name, spec)]
    per_layer = {
        m["name"]: (m, load_module(root / "bench" / "metrics"
                                   / f"{m['name']}.py",
                                   f"bench.metrics.{m['name']}"))
        for m in spec["per_layer"] if _reports(m, name, spec)}
    cell = Cell(name, w["chips"], config, traffic, app, e2e, per_layer)
    check_cell(cell)
    return cell


def check_cell(cell: Cell):
    """Refuse a configuration or traffic mix that states something this
    harness does not run, rather than measure something else."""
    from bench import reference
    cfg, tr = cell.config, cell.traffic
    wrong = []
    if cfg["mode"] != cell.app.MODE:
        wrong.append(f"mode {cfg['mode']!r}: the {cfg['app']!r} app runs "
                     f"{cell.app.MODE!r}")
    if cfg["dims"] != reference.DIMS:
        wrong.append(f"dims {cfg['dims']}: vectors are {reference.DIMS} "
                     "words, two 128-word accesses")
    if cfg["word_bits"] != 8:
        wrong.append(f"word_bits {cfg['word_bits']}: words are 8-b")
    if cfg["precision"] != "float32":
        wrong.append(f"precision {cfg['precision']!r}: the chain runs in "
                     "float32")
    if (cfg["n_banks"] * cfg["rows_per_bank"]) % cfg["rows_per_decision"]:
        wrong.append("rows_per_decision does not divide the stored rows")
    if tr["loop"] != "closed" or tr["callers"] != 1:
        wrong.append(f"traffic {tr['loop']!r} loop with {tr['callers']} "
                     "callers: the window is one closed-loop caller")
    if wrong:
        raise ValueError(f"cell {cell.name}: " + "; ".join(wrong))


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chip(chips: int):
    """The devices of a run: at least ``chips`` TPU chips, with the
    Pallas kernels lowered natively (no interpret mode)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     "device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    from repro.kernels._interpret import ENV_VAR, resolve_interpret
    if resolve_interpret(None):
        raise NoChip(f"${ENV_VAR} forces Pallas interpret mode on a TPU")
    return devs


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at one fixed directory: the one
    ``$JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` in the
    checkout.  Every program is cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the system under test, and the control that stands in for it
# ---------------------------------------------------------------------------

class Program:
    """The program's 32-bank substrate: ``MultiBankBackend`` with the
    Pallas inner, one fused banked launch per call."""

    def __init__(self, cell, st):
        from repro import dima
        from repro.core.params import DimaParams
        fields = {f.name for f in dataclasses.fields(DimaParams)}
        p = DimaParams(**{k: v for k, v in st["prm"].items()
                          if k in fields})
        self.be = dima.get_backend("multibank", p, st["chip"],
                                   inner="pallas",
                                   n_banks=st["cfg"]["n_banks"])
        self.mode = cell.app.MODE
        self.stored = st["stored"]
        self.v_range = tuple(st["v_range"])
        self.trim = (np.asarray(st["coef"], np.float32) if cell.app.TRIM
                     else None)

    def __call__(self, q, key):
        op = self.be.matvec if q.ndim == 1 else self.be.matmat
        out = op(self.stored, q, mode=self.mode, key=key,
                 v_range=self.v_range, trim=self.trim)
        return out.code, out.trimmed


class Control:
    """The plain reference in bfloat16, put in the program's place."""

    def __init__(self, cell, st):
        self.mode = cell.app.MODE
        self.st = st
        self.trim = cell.app.TRIM

    def __call__(self, q, key):
        from bench import reference
        one = q.ndim == 1
        st = self.st
        code, _, trimmed = reference.run(
            self.mode, st["stored"], q[None] if one else q, st["chip"], key,
            st["v_range"], st["coef"], st["prm"],
            n_banks=st["cfg"]["n_banks"], one=one, dtype="bfloat16")
        if one:
            code, trimmed = code[0], trimmed[0]
        return code, trimmed if self.trim else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _key(words):
    return np.asarray(words, np.uint32)


def setup(cell: Cell, seed: int, sizes: dict = None):
    """Data, silicon, calibration and the query pool on the device, all
    from ``seed``.  ``sizes`` overrides the configuration's and the
    traffic's sizes (the tests' small cells).  ``n_banks`` banks of
    ``rows_per_bank`` stored rows; a call of B queries gives B times
    rows / ``rows_per_decision`` decisions."""
    import jax
    from bench import reference
    cfg = {**cell.config, **(sizes or {})}
    tr = {**cell.traffic, **(sizes or {})}
    prm = cfg["params"]
    t0 = time.perf_counter()
    words = np.random.SeedSequence(seed).generate_state(8)
    k_data, k_chip, k_cal = (_key(words[i:i + 2]) for i in (0, 2, 4))
    n_stored = cfg["n_banks"] * cfg["rows_per_bank"]
    B = tr["queries_per_call"]
    n_pool = tr["pool_calls"] * B
    make = jax.jit(lambda kd, kc: (
        cell.app.make_data(kd, n_stored=n_stored,
                           n_queries=n_pool + tr["cal_queries"],
                           rows_per_decision=cfg["rows_per_decision"]),
        reference.sample_chip(kc, prm)))
    (stored, s_lab, queries, q_lab), chip = jax.block_until_ready(
        make(k_data, k_chip))
    t1 = time.perf_counter()
    cal_q, cal_lab = queries[n_pool:], q_lab[n_pool:]
    v_range, coef, aux = cell.app.calibrate(stored, s_lab, cal_q, cal_lab,
                                            chip, k_cal, cfg)
    log(f"set-up: data and silicon {t1 - t0:.3f} s, calibration "
        f"{time.perf_counter() - t1:.3f} s")
    if B == 1:
        pool = [queries[i] for i in range(n_pool)]
    else:
        pool = [queries[i * B:(i + 1) * B] for i in range(tr["pool_calls"])]
    jax.block_until_ready((stored, pool, chip))
    return dict(cfg=cfg, traffic=tr, prm=prm, stored=stored, chip=chip,
                v_range=v_range, coef=coef, aux=aux, pool=pool, B=B,
                M=n_stored, N=reference.DIMS,
                per_call=B * n_stored // cfg["rows_per_decision"],
                rng=np.random.default_rng(words[6:8]))


def window(cell, st, system, decide, seconds, seed, keep, trace_dir=None):
    """Closed loop, one caller: each call takes the next pool entry and a
    fresh noise key, and ends when its decisions are on the host.  Keeps
    ``keep`` calls' outputs, drawn uniformly from the seed (reservoir
    sampling), for the comparison after the window."""
    import jax
    pool, aux, rng = st["pool"], st["aux"], st["rng"]
    pick = random.Random(seed)
    kept, lat, failed = [], [], 0
    ann = jax.profiler.TraceAnnotation
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_opts())
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        key = rng.integers(0, 2 ** 32, 2, dtype=np.uint32)
        q = pool[i % len(pool)]
        t = time.perf_counter()
        try:
            with ann("bench.call"):
                code, trimmed = system(q, key)
            with ann("bench.decide"):
                dec = decide(code, trimmed, aux)
            with ann("bench.fetch"):
                dec = np.asarray(dec)
        except Exception as e:          # noqa: BLE001 — counted, reported
            failed += 1
            log(f"call {i} failed: {e!r}")
            i += 1
            continue
        lat.append(time.perf_counter() - t)
        rec = (i, key, code, trimmed, dec)
        if len(kept) < keep:
            kept.append(rec)
        else:
            j = pick.randrange(i + 1)
            if j < keep:
                kept[j] = rec
        i += 1
    elapsed = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return dict(calls=i, done=len(lat), failed=failed, lat=lat,
                elapsed=elapsed, kept=sorted(kept, key=lambda r: r[0]))


def _trace_opts():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def end_to_end(w, per_call, setup_s):
    """Every decision of a call is on the host when the call ends, so the
    percentiles of the calls' latencies are those of the decisions'."""
    lat_ms = [x * 1e3 for x in w["lat"]]
    return {
        "setup_s": (setup_s, "s"),
        "decisions_per_s": (w["done"] * per_call / w["elapsed"],
                            "decisions/s"),
        "decision_p50_ms": (float(np.percentile(lat_ms, 50)), "ms"),
        "decision_p95_ms": (float(np.percentile(lat_ms, 95)), "ms"),
    }


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def compare(cell, st, kept, decide):
    """Recompute every kept call with the plain reference on the same
    stored data, queries and keys, and compare what the program gave.
    Returns {number: value}; nothing where no call was kept."""
    import jax.numpy as jnp
    from bench import reference
    if not kept:
        return {}
    mode, M, pool = cell.app.MODE, st["M"], st["pool"]
    nb = st["cfg"]["n_banks"]
    queries = [pool[i % len(pool)] for i, *_ in kept]
    keys = [k for _, k, *_ in kept]
    if st["B"] == 1:
        want = [reference.run_many(
            mode, st["stored"], jnp.stack(queries), st["chip"],
            jnp.asarray(np.stack(keys)), st["v_range"], st["coef"],
            st["prm"], n_banks=nb)]
    else:
        want = [reference.run(mode, st["stored"], q, st["chip"], k,
                              st["v_range"], st["coef"], st["prm"],
                              n_banks=nb)
                for q, k in zip(queries, keys)]
    w_code = np.concatenate([np.asarray(c) for c, _, _ in want])
    w_dec = np.concatenate([
        np.asarray(decide(c, t if cell.app.TRIM else None, st["aux"])
                   ).reshape(len(c), -1) for c, _, t in want])
    g_code = np.concatenate([np.asarray(r[2]).reshape(-1, M) for r in kept])
    g_dec = np.concatenate([np.asarray(r[4]).reshape(st["B"], -1)
                            for r in kept])
    out = {"code_mismatch_share": float(np.mean(g_code != w_code)),
           "decision_mismatch_share": float(np.mean(g_dec != w_dec))}
    if cell.app.TRIM:
        # the trim epilogue alone: the program's trimmed scores against
        # the reference's trim of the program's own codes
        g_trim = np.concatenate([np.asarray(r[3]).reshape(-1, M)
                                 for r in kept]).astype(np.float64)
        q_sum = np.concatenate([np.asarray(q).reshape(-1, reference.DIMS)
                                for q in queries]).sum(-1)
        want_trim = np.asarray(reference.trim(
            mode, jnp.asarray(g_code),
            jnp.asarray(q_sum, jnp.float32)[:, None],
            jnp.asarray(st["coef"], jnp.float32),
            jnp.asarray(st["v_range"], jnp.float32), st["prm"]),
            np.float64)
        out["trim_gap_rel"] = float(np.abs(g_trim - want_trim).max()
                                    / np.abs(want_trim).max())
    return out


def judge(numbers: dict, limits: dict, failed: int):
    """(correct, [[name, value, limit]]): every number that has a limit
    within it, and no call failed."""
    rows = [[k, numbers.get(k, float("nan")), lim]
            for k, lim in limits.items()]
    ok = failed == 0 and all(np.isfinite(v) and v <= lim
                             for _, v, lim in rows)
    return bool(ok), rows


# ---------------------------------------------------------------------------
# the whole run
# ---------------------------------------------------------------------------

def prepare(cell: Cell, seed: int, *, sizes: dict = None,
            system_cls=Program):
    """Set up from ``seed`` and warm up every shape the window uses:
    (state, system under test, jitted decision)."""
    import jax
    st = setup(cell, seed, sizes)
    system = system_cls(cell, st)
    decide = jax.jit(cell.app.decide)
    warm = np.asarray([0xFFFFFFFF, 0], np.uint32)
    t0 = time.perf_counter()
    for _ in range(2):          # compile, then the steady path once
        code, trimmed = system(st["pool"][0], warm)
        dec = np.asarray(decide(code, trimmed, st["aux"]))
    if dec.size != st["per_call"]:
        raise ValueError(f"a call gave {dec.size} decisions, the "
                         f"configuration states {st['per_call']}")
    log(f"set-up: warm-up {time.perf_counter() - t0:.3f} s")
    # set-up leaves some million objects (JAX, the program, the data);
    # collect its garbage once and keep the rest out of the collector's
    # full passes, which would otherwise rescan it inside the window
    gc.collect()
    gc.freeze()
    return st, system, decide


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, sizes: dict = None, system_cls=Program, check_chip=True):
    """Set up, warm up, measure for ``seconds``, compare, and return the
    result line's object."""
    import jax
    if check_chip:
        require_chip(cell.chips)
        log(f"set-up: devices up at {time.perf_counter() - t_start:.3f} s")
    st, system, decide = prepare(cell, seed, sizes=sizes,
                                 system_cls=system_cls)
    keep = st["traffic"]["compared_calls"]
    setup_s = time.perf_counter() - t_start
    log(f"set-up in all {setup_s:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        w = window(cell, st, system, decide, seconds, seed, keep, trace_dir)
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": jax.device_count(),
                  "memory_peak_bytes": stats.get("peak_bytes_in_use")}
        breakdown = None
        if trace:
            metrics, device_t, breakdown = _per_layer(cell, st, w,
                                                      trace_dir)
            device.update(device_t)
        else:
            e2e = end_to_end(w, st["per_call"], setup_s)
            metrics = {k: {"value": e2e[k][0], "unit": e2e[k][1]}
                       for k in cell.end_to_end}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del system
    if w["lat"]:
        lat = np.asarray(w["lat"]) * 1e3
        p50 = float(np.median(lat))
        slow = lat > 2 * p50
        log(f"window: {w['done']} calls in {w['elapsed']:.3f} s, latency "
            f"p50 {p50:.3f} ms, max {lat.max():.3f} ms, {int(slow.sum())} "
            f"calls over twice the p50 taking {lat[slow].sum() / 1e3:.3f} s")
    t0 = time.perf_counter()
    numbers = compare(cell, st, w["kept"], decide)
    log(f"compared {len(w['kept'])} of {w['done']} calls with the "
        f"reference in {time.perf_counter() - t0:.3f} s")
    correct, rows = judge(numbers, cell.config["limits"], w["failed"])
    result = {"correct": correct, "attempted": w["calls"] * st["per_call"],
              "failed": w["failed"] * st["per_call"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    return result


def _per_layer(cell, st, w, trace_dir):
    import jax
    from bench import trace_reduce as tr
    s = tr.summarize(tr.load(trace_dir))
    busy = tr.busy_s(s)
    ctx = {"summary": s, "busy_s": busy, "window_s": w["elapsed"],
           "calls": w["done"], "decisions": w["done"] * st["per_call"],
           "B": st["B"], "M": st["M"], "N": st["N"],
           "device_kind": jax.devices()[0].device_kind,
           "mode": cell.app.MODE}
    metrics = {}
    for name, (entry, reader) in cell.per_layer.items():
        v = reader.read(ctx)
        if v is not None:
            metrics[name] = {"value": v, "unit": entry["unit"]}
    breakdown = {"device_ops": tr.top_ops(s), "idle_gaps": tr.idle_gaps(s)}
    return metrics, {"busy_s": busy, "window_s": w["elapsed"]}, breakdown
