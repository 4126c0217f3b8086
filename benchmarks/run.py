"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows followed by the detailed
tables. ``PYTHONPATH=src python -m benchmarks.run``

``--smoke`` runs the harness end-to-end at tiny sizes (CI keeps it from
rotting): the figure benches that are pure model arithmetic, plus the
matvec/multibank/crossover sweeps on small matrices — written to
BENCH_dima_api.smoke.json so toy numbers never overwrite the committed
full-size artifact.  Every run (smoke included) asserts the fused
multibank matvec issues exactly ONE compiled-computation launch
(``dima.count_dispatches``) — a platform-independent guard against the
per-bank loop silently regressing the shipped path.

BENCH_dima_api.json carries, besides the loop-vs-vectorized matvec
numbers, the single-bank vs multibank comparison (``multibank``), the
platform-keyed ``crossover`` section (reference↔pallas crossover per
``jax.default_backend()`` — the entry ``repro.dima.get_backend("auto")``
reads on the next run; the legacy flat ``auto_crossover_rows`` tag pair
is still written for old readers) and the platform-keyed ``kernels``
section (the fused-epilogue vs separate-ops comparison).  Platform
sections deep-merge on write: a CPU run updates ``crossover["cpu"]``
without clobbering a TPU measurement sitting next to it.
BENCH_serving.json (bench_serving.py) carries the continuous-engine vs
sequential-oracle comparison, and the ``analog_lm`` key of
BENCH_dima_api.json (bench_lm_analog.py, merged read-modify-write) the
end-to-end analog decode row.  Artifact schemas: docs/benchmarks.md.
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks._timing import timed as _shared_timed

#: platform-keyed sections of BENCH_dima_api.json — merged per platform
#: on write instead of replaced wholesale
_PLATFORM_SECTIONS = ("crossover", "kernels")


def _timed(fn):
    return _shared_timed(fn, warmup=1, k=1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, skip the slow app/roofline benches")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import bench_apps, bench_conventional, bench_dima
    from benchmarks import bench_serving, roofline

    rows = []
    details = {}

    fig3, us = _timed(bench_dima.fig3_mrfr_inl)
    rows.append(("fig3_mrfr_inl", us, f"max_inl={fig3['max_inl_lsb']}LSB"))
    details["fig3"] = fig3

    if not args.smoke:
        fig4, us = _timed(bench_dima.fig4_blp_cblp_error)
        rows.append(("fig4_blp_cblp_error", us,
                     f"dp={fig4['dp_max_err_pct']}%/"
                     f"md={fig4['md_max_err_pct']}%"))
        details["fig4"] = fig4

        fig5, us = _timed(bench_dima.fig5_energy_accuracy_tradeoff)
        rows.append(("fig5_energy_accuracy", us,
                     f"sweep_points={len(fig5['sweep'])}"))
        details["fig5"] = fig5

        fig6, us = _timed(bench_apps.fig6_application_table)
        worst_gap = max(r["gap_pct"] for r in fig6)
        rows.append(("fig6_applications", us, f"worst_acc_gap={worst_gap}%"))
        details["fig6"] = fig6

    fig7, us = _timed(bench_dima.fig7_chip_summary)
    rows.append(("fig7_chip_summary", us,
                 f"mf={fig7['mf']['energy_pj']}pJ/dec"))
    details["fig7"] = fig7

    conv, us = _timed(bench_conventional.access_and_throughput)
    rows.append(("conventional_comparison", us,
                 f"access_red={conv['access_reduction_x']}x"))
    details["conventional"] = conv

    api = bench_dima.bench_matvec_api(
        **({"m": 256, "m_loop": 8} if args.smoke else {}))
    rows.append(("dima_api_matvec", api["vectorized_us_per_call"],
                 f"loop/vec_speedup={api['speedup_x']}x"))

    mb = bench_dima.bench_multibank(
        **({"m": 512, "n_banks": 8} if args.smoke else {}))
    api["multibank"] = mb
    rows.append(("dima_multibank", mb["multibank_us_per_call"],
                 f"banks={mb['n_banks']};"
                 f"dispatches={mb['multibank_dispatches']}"
                 f"vs{mb['multibank_loop_dispatches']};"
                 f"fused_speedup={mb['fused_speedup_x']}x;"
                 f"pJ={mb['multibank_pj_per_decision']};"
                 f"savings={mb['energy_savings_x']}x"))
    # perf smoke guard (runs in CI via --smoke, and on full runs too —
    # it is platform-independent): the fused multibank matvec must issue
    # exactly ONE compiled-computation launch, and the loop oracle one
    # per bank, so the per-bank Python loop can never silently creep
    # back into the shipped path behind a plausible-looking timing
    if mb["multibank_dispatches"] != 1:
        raise RuntimeError(
            f"fused multibank matvec issued {mb['multibank_dispatches']} "
            f"dispatches, expected 1 — the bank axis is no longer fused "
            f"(full record: {mb})")
    if mb["multibank_loop_dispatches"] != mb["n_banks"]:
        raise RuntimeError(
            f"per-bank loop oracle issued "
            f"{mb['multibank_loop_dispatches']} dispatches, expected "
            f"n_banks={mb['n_banks']} — the dispatch counter or the "
            f"oracle changed meaning (full record: {mb})")

    fe = bench_dima.bench_fused_epilogue(
        **({"m": 512, "n_banks": 8} if args.smoke else {}))
    rows.append(("dima_fused_epilogue", fe["fused_us_per_call"],
                 f"separate={fe['separate_us_per_call']}us;"
                 f"delta={fe['delta_us']}us;"
                 f"dispatches={fe['fused_dispatches']}"))
    # the flagship guard: the trimmed matvec with the calibration
    # epilogue fused must still be ONE compiled-computation launch —
    # platform-independent, asserted in CI via --smoke
    if fe["fused_dispatches"] != 1:
        raise RuntimeError(
            f"fused-epilogue matvec issued {fe['fused_dispatches']} "
            f"dispatches, expected 1 — the trim epilogue fell out of the "
            f"kernel launch (full record: {fe})")

    cross = bench_dima.bench_auto_crossover(
        row_counts=(32, 128) if args.smoke else (16, 32, 64, 128, 256, 512))
    platform = cross["auto_crossover_platform"]
    api["crossover"] = {platform: {
        "rows": cross["auto_crossover_rows"],
        "sweep": cross["sweep"],
    }}
    api["kernels"] = {platform: {"fused_epilogue": fe}}
    # legacy flat tags, still consumed by pre-platform-section readers
    api["auto_crossover"] = cross["sweep"]
    api["auto_crossover_rows"] = cross["auto_crossover_rows"]
    api["auto_crossover_platform"] = platform
    rows.append(("dima_auto_crossover", 0,
                 f"min_rows={cross['auto_crossover_rows']}"))

    # continuous engine vs the one-slot sequential oracle, plus paged vs
    # dense KV at matched memory, under Poisson traces — merged into the
    # BENCH_serving(.smoke).json artifact (the fleet section is owned by
    # full bench_serving runs / repro.launch.replicas, not re-measured
    # here)
    serving = bench_serving.compare(smoke=args.smoke)
    paged = bench_serving.compare_paged(smoke=args.smoke)
    bench_serving.write_json({"scheduler": serving, "paged": paged},
                             smoke=args.smoke)
    rows.append(("serving_continuous", 0,
                 f"continuous/sequential={serving['speedup_tokens_per_s']}x;"
                 f"p99={serving['continuous']['latency_p99_s']}s"))
    rows.append(("serving_paged", 0,
                 f"paged/dense={paged['speedup_tokens_per_s']}x@"
                 f"{paged['matched_memory_rows']}rows;"
                 f"skips={paged['paged']['prefill_skips']};"
                 f"cow={paged['paged']['cow_copies']}"))
    details["serving"] = {"scheduler": serving, "paged": paged}

    details["dima_api"] = api
    # full runs refresh the committed repo-root artifact (which
    # AutoBackend reads for its measured crossover); --smoke writes a
    # side file so CI / local smoke passes never overwrite real
    # measurements with toy-size numbers
    # (merged read-modify-write: bench_lm_analog.py owns the
    # ``analog_lm`` key of the same file — don't clobber it)
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
    name = "BENCH_dima_api.smoke.json" if args.smoke else "BENCH_dima_api.json"
    path = os.path.join(root, name)
    merged = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    # platform-keyed sections merge per platform (a CPU run must not
    # clobber the TPU crossover measured elsewhere); everything else is
    # replaced wholesale as before
    for sect in _PLATFORM_SECTIONS:
        prior = merged.get(sect)
        if sect in api and isinstance(prior, dict):
            prior.update(api[sect])
            api[sect] = prior
    merged.update(api)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)

    roof = []
    if not args.smoke:
        def _roofline():
            return roofline.table("pod16x16")
        roof, us = _timed(_roofline)
        if roof:
            worst = min(roof, key=lambda r: r["roofline_frac"])
            rows.append(("roofline_baseline", us,
                         f"cells={len(roof)};worst={worst['arch']}/"
                         f"{worst['shape']}={worst['roofline_frac']:.3f}"))
        details["roofline_cells"] = len(roof)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")

    print("\n=== details ===")
    print(json.dumps(details, indent=1, default=str)[:8000])
    if roof:
        print("\n=== roofline (single-pod baseline) ===")
        print(roofline.render_markdown(roof))


if __name__ == "__main__":
    main()
