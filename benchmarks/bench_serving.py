"""Serving benchmarks: scheduler, KV layout, and fleet tiers.

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]
        [--sections scheduler,paged,replicas]

Three sections, each a key of ``BENCH_serving.json`` (merged
read-modify-write, so partial runs never clobber the other sections):

* ``scheduler`` — continuous batching vs the sequential oracle (both
  dense, both the SAME engine; the baseline is simply ``max_batch=1``).
  Isolates pure scheduling efficiency; greedy decode keeps the tokens
  identical.
* ``paged`` — paged vs dense KV at **matched memory**: the paged pool
  holds exactly the token capacity of the dense ``(max_batch, max_len)``
  table and the same slot-table width, so the comparison isolates the
  layout (gather/scatter decode, prefix sharing, prefill skips) rather
  than batch-width compute.  The
  trace is template-heavy (``launch/replicas.make_shared_trace``: shared
  few-shot headers + recurring prompts — the traffic prefix reuse
  exists for); tokens are asserted bitwise identical to the dense run,
  and the decode jit is asserted to have traced exactly once.
* ``replicas`` — the fleet tier under open-loop Poisson load at
  ``--rate-x`` (default 10×) the measured single-dense-engine request
  rate: 1×dense vs 1×paged vs 2×paged replica processes behind one
  FIFO (``launch/replicas.run_fleet``), reporting fleet tokens/s,
  p50/p99 latency, SLO attainment and per-replica utilization.
  ``fleet_speedup_x`` is 2×paged over 1×dense.

Arrivals for the single-engine sections are expressed in *logical
decode steps* (deterministic, platform-independent interleaving); the
fleet section is wall-clock open-loop by construction.  ``--smoke``
writes the gitignored ``BENCH_serving.smoke.json`` side file and skips
the fleet section (CI runs ``python -m repro.launch.replicas --smoke``
as its own step).  ``$DIMA_BENCH_SERVING_JSON`` overrides the output
path.  Schema: docs/benchmarks.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np


def make_trace(seed=0, n_requests=32, vocab=256, *, max_batch=8,
               prompt_lens=(4, 24), max_news=(1, 24)):
    """Deterministic ragged trace: (prompts, max_new, arrival_steps).

    Mean inter-arrival ≈ E[max_new] / max_batch · 0.8 logical steps —
    offered load just under slot capacity, so the continuous scheduler
    stays busy while the sequential baseline queues."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, int(rng.integers(*prompt_lens))
                            ).astype(np.int32) for _ in range(n_requests)]
    max_new = rng.integers(max_news[0], max_news[1] + 1,
                           n_requests).astype(int)
    arrivals = _arrivals(max_new, seed, max_batch)
    return prompts, max_new, arrivals


def _arrivals(max_new, seed, max_batch):
    rng = np.random.default_rng(seed + 1000)
    mean_gap = float(np.mean(max_new)) / max_batch * 0.8
    return np.cumsum(rng.exponential(mean_gap, len(max_new)))


def run_trace(model, params, trace, *, max_batch=8, bucket=8, max_len=64,
              kv="dense", block_size=16, kv_blocks=None, engine=None):
    """Drain one trace through one engine configuration; returns metrics.

    Pass ``engine`` to reuse a drained engine across runs: a fresh engine
    re-jits (new closures), so a timed run on one would measure XLA
    compile time, not serving — callers warm an engine with one full
    drain, then time the second (steady state: jits compiled AND, for
    paged, the prefix registry warm, exactly like a long-running
    server)."""
    from repro.inference import Request, ServeEngine

    prompts, max_new, arrivals = trace
    eng = engine if engine is not None else ServeEngine(
        model, params, bucket=bucket, max_batch=max_batch, max_len=max_len,
        kv=kv, block_size=block_size, kv_blocks=kv_blocks)
    base = dict(eng.stats)                # reuse = cumulative stats: delta
    reqs = [Request(rid=i, prompt=p.copy(), max_new=int(m))
            for i, (p, m) in enumerate(zip(prompts, max_new))]
    clock = 0.0                       # logical decode steps executed
    prev_clock, prev_wall = 0.0, time.time()
    i = 0
    done = []
    t0 = time.perf_counter()
    while i < len(reqs) or eng.busy:
        now = time.time()
        while i < len(reqs) and arrivals[i] <= clock:
            # the request became logically visible somewhere inside the
            # last blocking engine call (prev_clock, clock]: stamp the
            # interpolated wall time, not "after the call returned" —
            # otherwise the sequential path's queue wait (the very thing
            # this benchmark measures) would be cut out of its latency
            frac = ((arrivals[i] - prev_clock) / (clock - prev_clock)
                    if clock > prev_clock else 1.0)
            reqs[i].submitted_at = prev_wall + frac * (now - prev_wall)
            eng.submit(reqs[i])
            i += 1
        if not eng.busy:
            prev_clock, prev_wall = clock, time.time()
            clock = float(arrivals[i])        # jump to the next arrival
            continue
        prev_clock, prev_wall = clock, time.time()
        done.extend(eng.step())
        clock += 1
    wall = time.perf_counter() - t0
    lat = np.array([r.done_at - r.submitted_at for r in done])
    assert len(done) == len(reqs)
    stats = {k: eng.stats[k] - base[k] for k in eng.stats}
    assert stats["tokens"] == sum(len(r.out) for r in done)
    if stats["steps"] > 1:
        # trace-count stability: however slots churned (including across
        # reused-engine drains), ONE decode trace — a retrace would mean
        # the block table leaked a shape
        assert eng.jit_traces["decode"] == 1, eng.jit_traces
    m = {
        "kv": eng.kv,
        "max_batch": eng.max_batch,
        "requests": len(done),
        "tokens": stats["tokens"],
        "wall_s": round(wall, 4),
        "tokens_per_s": round(stats["tokens"] / wall, 2),
        "latency_p50_s": round(float(np.percentile(lat, 50)), 4),
        "latency_p99_s": round(float(np.percentile(lat, 99)), 4),
        "decode_steps": stats["steps"],
        "outputs": {r.rid: list(r.out) for r in done},
    }
    if eng.kv == "paged":
        m["kv_blocks"] = eng.kv_blocks
        for k in ("prefix_hits", "prefill_skips", "cow_copies", "kv_waits"):
            m[k] = stats[k]
    return m


def _model(arch="gemma3-1b"):
    import jax

    from repro.configs import RunConfig, get_arch, reduced
    from repro.models import LM

    cfg = dataclasses.replace(reduced(get_arch(arch)), dtype="float32")
    model = LM(cfg, RunConfig())
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _assert_identical(rec_a, rec_b, what):
    # pop BEFORE comparing (never inside an assert: under `python -O` the
    # side effects would vanish too, leaking per-request outputs into the
    # artifact and skipping the parity check)
    out_a = rec_a.pop("outputs")
    out_b = rec_b.pop("outputs")
    if out_a != out_b:
        raise RuntimeError(f"{what} diverged: greedy decode must be "
                           f"token-identical")


def compare(smoke=False, seed=0, arch="gemma3-1b", max_batch=8):
    """scheduler section: continuous (max_batch slots) vs sequential
    (one slot), both dense, after a warm-up pass that compiles every
    shape the trace touches; token-identical outputs verified."""
    import jax

    cfg, model, params = _model(arch)
    n = 6 if smoke else 32
    trace = make_trace(seed, n, cfg.vocab_size, max_batch=max_batch)

    from repro.inference import ServeEngine

    results = {}
    for label, mb in (("sequential", 1), ("continuous", max_batch)):
        # warm-up = a full identical drain OF THE SAME ENGINE: greedy
        # decode is deterministic, so this compiles exactly the (B, blen)
        # prefill/decode shapes the timed run will hit, and the timed
        # drain measures steady-state serving, not XLA compile
        eng = ServeEngine(model, params, bucket=8, max_batch=mb,
                          max_len=64, kv="dense")
        run_trace(model, params, trace, engine=eng)
        results[label] = run_trace(model, params, trace, engine=eng)
    _assert_identical(results["sequential"], results["continuous"],
                      "schedulers")
    return {
        "platform": jax.default_backend(),
        "arch": cfg.name,
        "max_batch": max_batch,
        "trace": {"seed": seed, "n_requests": n,
                  "total_tokens": results["continuous"]["tokens"]},
        "sequential": results["sequential"],
        "continuous": results["continuous"],
        "speedup_tokens_per_s": round(
            results["continuous"]["tokens_per_s"]
            / results["sequential"]["tokens_per_s"], 3),
        "speedup_decode_steps": round(
            results["sequential"]["decode_steps"]
            / results["continuous"]["decode_steps"], 3),
    }


def compare_paged(smoke=False, seed=0, arch="gemma3-1b", *, max_batch=8,
                  max_len=64, bucket=32, block_size=16):
    """paged section: paged vs dense at matched KV memory and slot-table
    width on a template-heavy trace.  The dense table holds
    max_batch·max_len token rows; the paged pool holds exactly the same
    (kv_blocks · block_size), with shared prefixes stored once and
    duplicate prompts skipping their prefill entirely."""
    import jax

    from repro.launch.replicas import make_shared_trace

    cfg, model, params = _model(arch)
    n = 8 if smoke else 32
    prompts, max_new = make_shared_trace(
        n, seed=seed, vocab=cfg.vocab_size, n_templates=3,
        template_len=28, suffix_len=4, max_news=(2, 10) if smoke else (4, 16),
        dup_frac=0.5)
    trace = (prompts, max_new, _arrivals(max_new, seed, max_batch))
    rows = max_batch * max_len                # dense KV token capacity
    kv_blocks = rows // block_size

    from repro.inference import ServeEngine

    arms = {
        "dense": dict(kv="dense"),
        "paged": dict(kv="paged", block_size=block_size,
                      kv_blocks=kv_blocks),
    }
    results = {}
    for label, kw in arms.items():
        # same-engine warm drain, then the timed drain: steady state —
        # jits compiled, and (paged) the prefix registry warm, exactly
        # like a long-running server seeing recurring prompts
        eng = ServeEngine(model, params, bucket=bucket, max_batch=max_batch,
                          max_len=max_len, **kw)
        run_trace(model, params, trace, engine=eng)
        results[label] = run_trace(model, params, trace, engine=eng)
    _assert_identical(results["dense"], results["paged"], "KV layouts")
    return {
        "platform": jax.default_backend(),
        "arch": cfg.name,
        "matched_memory_rows": rows,
        "block_size": block_size,
        "trace": {"seed": seed, "n_requests": n, "dup_frac": 0.5,
                  "n_templates": 3,
                  "total_tokens": results["paged"]["tokens"]},
        "dense": results["dense"],
        "paged": results["paged"],
        "speedup_tokens_per_s": round(
            results["paged"]["tokens_per_s"]
            / results["dense"]["tokens_per_s"], 3),
        "speedup_decode_steps": round(
            results["dense"]["decode_steps"]
            / results["paged"]["decode_steps"], 3),
    }


def fleet(seed=0, *, rate_x=10.0, n_requests=48, max_batch=8, max_len=64,
          bucket=32, slo_ms=2000.0, base_rps=None):
    """replicas section: open-loop Poisson load at ``rate_x`` × the
    measured single-dense-engine request rate, swept over 1×dense /
    1×paged / 2×paged replica fleets on one shared FIFO.  Runs before
    anything in this process touches JAX: on a TPU host each replica
    process holds its own chip."""
    from repro.inference import chain_key, tail_key
    from repro.launch.replicas import make_shared_trace, run_fleet

    # short decisions (2-8 generated tokens): the paper's workload is
    # per-DECISION inference, so fleet requests are classification-sized
    # answers over shared few-shot templates — the regime where paged
    # admission (prefix pages mapped, duplicate prefills skipped) moves
    # fleet throughput rather than being diluted by long decode tails
    trace = make_shared_trace(n_requests, seed=seed, dup_frac=0.5,
                              max_news=(2, 8))
    # the serving tier sizes the paged pool for its traffic: the dense-
    # table equivalent (live decode) plus the trace's distinct prefix
    # blocks, so the idle LRU can keep the hot prefix set warm instead
    # of churning it on every admission.  Dense cannot spend that memory
    # at all (its per-slot layout is fixed and admission is slot-bound);
    # the matched-memory comparison is the ``paged`` section's job.
    bs = 16
    hot = set()
    for p in trace[0]:
        blen = -(-len(p) // bucket) * bucket
        padded = np.full(blen, p[0], np.int32)
        padded[blen - len(p):] = p
        for j in range(-(-blen // bs)):
            hot.add(chain_key(padded, j, bs) if (j + 1) * bs <= blen
                    else tail_key(padded, blen))
    kv_blocks = max_batch * max_len // bs + len(hot)
    # two discarded warm passes: with >1 replica a single pass leaves
    # each per-replica prefix registry covering only the requests it
    # happened to pull, so the timed pass would measure cold prefills
    # that a steady-state server (which has seen its traffic mix many
    # times over) would not pay
    common = dict(max_batch=max_batch, max_len=max_len, bucket=bucket,
                  slo_ms=slo_ms, trace=trace, seed=seed, warm_passes=2)
    if base_rps is None:
        # calibrate: a closed-loop 1×dense drain (requests arrive
        # immediately) measures the engine's intrinsic request rate
        cal = run_fleet(n_replicas=1, kv="dense", rate_rps=1e6, **common)
        base_rps = cal["requests"] / cal["wall_s"]
    rate = rate_x * base_rps

    sweep = {}
    for label, n_rep, kv in (("dense_x1", 1, "dense"),
                             ("paged_x1", 1, "paged"),
                             ("paged_x2", 2, "paged")):
        # the paged fleet dispatches by prompt affinity: per-replica
        # prefix registries are private, so duplicates must land on the
        # replica that owns their pages (single-replica arms are
        # routing-invariant; greedy tokens are identical either way)
        sweep[label] = run_fleet(n_replicas=n_rep, kv=kv, rate_rps=rate,
                                 kv_blocks=kv_blocks if kv == "paged"
                                 else None,
                                 affinity="prompt" if kv == "paged"
                                 else None, **common)
    return {
        "platform": sweep["dense_x1"]["platform"],
        "base_rps": round(float(base_rps), 3),
        "rate_x": rate_x,
        "offered_rps": round(float(rate), 3),
        "slo_ms": slo_ms,
        "kv_blocks": kv_blocks,
        "hot_prefix_blocks": len(hot),
        "trace": {"seed": seed, "n_requests": n_requests, "dup_frac": 0.5,
                  "max_news": [2, 8]},
        "sweep": sweep,
        "fleet_speedup_x": round(
            sweep["paged_x2"]["fleet_tokens_per_s"]
            / sweep["dense_x1"]["fleet_tokens_per_s"], 3),
    }


def write_json(sections: dict, smoke=False):
    """Merge ``sections`` into the serving artifact read-modify-write —
    a scheduler-only run must not clobber a committed fleet sweep."""
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
    name = "BENCH_serving.smoke.json" if smoke else "BENCH_serving.json"
    path = os.environ.get("DIMA_BENCH_SERVING_JSON",
                          os.path.join(root, name))
    rec = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (json.JSONDecodeError, OSError):
            rec = {}
    if "sequential" in rec and "scheduler" not in rec:
        rec = {"scheduler": rec}              # migrate the pre-PR7 layout
    rec.update(sections)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny traces, side-file output, no fleet section")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--rate-x", type=float, default=10.0,
                    help="fleet offered load, × the measured dense rate")
    ap.add_argument("--sections", default=None,
                    help="comma list: scheduler,paged,replicas "
                         "(default: all; --smoke drops replicas)")
    args = ap.parse_args(argv)
    wanted = (args.sections.split(",") if args.sections else
              ["scheduler", "paged"] + ([] if args.smoke else ["replicas"]))

    sections = {}
    # the fleet first: its replica processes need the chips this process
    # would hold once the single-engine sections below touch JAX
    if "replicas" in wanted:
        sections["replicas"] = fleet(seed=args.seed, rate_x=args.rate_x,
                                     max_batch=args.max_batch)
        f = sections["replicas"]
        print(f"[bench_serving] fleet @ {f['offered_rps']} rps "
              f"({f['rate_x']}x): paged_x2/dense_x1 = "
              f"{f['fleet_speedup_x']}x tokens/s, SLO "
              f"{f['sweep']['paged_x2']['slo_attainment']:.0%} vs "
              f"{f['sweep']['dense_x1']['slo_attainment']:.0%}")
    if "scheduler" in wanted:
        sections["scheduler"] = compare(smoke=args.smoke, seed=args.seed,
                                        max_batch=args.max_batch)
        print(f"[bench_serving] scheduler: continuous/sequential "
              f"{sections['scheduler']['speedup_tokens_per_s']}x tokens/s "
              f"({sections['scheduler']['speedup_decode_steps']}x steps)")
    if "paged" in wanted:
        sections["paged"] = compare_paged(smoke=args.smoke, seed=args.seed,
                                          max_batch=args.max_batch)
        p = sections["paged"]
        print(f"[bench_serving] paged: {p['speedup_tokens_per_s']}x tokens/s"
              f" vs dense at {p['matched_memory_rows']} KV rows "
              f"(skips={p['paged']['prefill_skips']}, "
              f"hits={p['paged']['prefix_hits']}, "
              f"cow={p['paged']['cow_copies']})")
    path = write_json(sections, smoke=args.smoke)
    print(f"[bench_serving] -> {path}")
    return sections


if __name__ == "__main__":
    main()
