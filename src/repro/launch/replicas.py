"""Multi-replica serving tier: N data-parallel ``ServeEngine`` replicas
behind one shared FIFO, under open-loop (Poisson wall-clock) load.

    PYTHONPATH=src python -m repro.launch.replicas --replicas 2 \
        --rate 20 --requests 48            # or just: --smoke

Each replica is a separate *process* (spawn, not fork: the per-replica
environment must be set before its jax initializes) running its own
paged-KV ``ServeEngine`` over identically-initialized params.  On a TPU
host each replica is pinned to its own chip (``replica_env``), so a
fleet needs as many visible chips as replicas, and the parent process
stays off JAX until its replicas have exited (a process that has
initialized JAX holds every chip it sees).  A single
``multiprocessing.Queue`` is the fleet's FIFO: replicas race to pull,
so a hot replica with free blocks naturally takes more of the load and
no request is ever assigned to a stalled engine.  ``--affinity prompt``
switches to prefix-affinity dispatch (one queue per replica, routed by
a stable hash of the prompt bytes), so duplicate prompts always land on
the replica whose private prefix registry already holds them — the
paged fleet's steady-state configuration.  Workers signal readiness
only after a warm-up drain, so compile time never pollutes latency
percentiles.

Load is open-loop: arrivals are a Poisson process in *wall time* at
``--rate`` req/s, submitted whether or not the fleet keeps up — queue
growth shows up as latency, exactly like a real ingress.  The report
(``run_fleet``) carries fleet tokens/s, request-latency p50/p99, SLO
attainment (fraction of requests finishing within ``--slo-ms``), and
per-replica utilization (busy wall fraction + engine stats, paged
counters included).  ``--smoke`` runs 2 tiny replicas and asserts the
fleet's tokens are identical to a local sequential dense-oracle drain
(greedy decode is batching- and replica-invariant), which is the CI
gate.  ``benchmarks/bench_serving.py`` drives the same ``run_fleet``
for the committed BENCH_serving.json replica sweep.
"""
from __future__ import annotations

import argparse
import glob
import json
import multiprocessing as mp
import multiprocessing.connection as mp_connection
import os
import queue as queue_mod
import socket
import sys
import time
import zlib

import numpy as np

READY_TIMEOUT_S = 600.0     # per-replica model build + warm-up compile
POLL_S = 0.002              # idle worker poll interval


#: PCI device ids of TPU chips (Google vendor id 0x1ae0); other Google
#: PCI functions, such as the gVNIC network card, are not chips
_TPU_PCI_IDS = {"0x0027", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"}


def visible_tpu_chips() -> int:
    """TPU chips this host exposes, counted on the PCI bus (or, where
    /sys hides it, as /dev/accel* or /dev/vfio device nodes) — without
    initializing JAX, which would claim them for this process."""
    n = 0
    for dev in glob.glob("/sys/bus/pci/devices/*"):
        try:
            with open(os.path.join(dev, "vendor")) as f:
                vendor = f.read().strip()
            with open(os.path.join(dev, "device")) as f:
                device = f.read().strip()
        except OSError:
            continue
        n += vendor == "0x1ae0" and device in _TPU_PCI_IDS
    return max(n, len(glob.glob("/dev/accel[0-9]*")),
               len(glob.glob("/dev/vfio/[0-9]*")))


def tpu_fleet() -> bool:
    """True when replicas will run on TPU chips: the host has chips and
    ``$JAX_PLATFORMS`` (if set) allows the TPU."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    return visible_tpu_chips() > 0 and (
        not plats or "tpu" in plats.split(","))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def replica_env(idx: int, port: int = None) -> dict:
    """Per-replica process environment: silence TF/tcmalloc chatter
    (tcmalloc itself is LD_PRELOADed by the operator when present, so
    only its report threshold is set) and, given the ``port`` of its
    own single-chip slice, pin the replica to TPU chip ``idx``."""
    env = {
        "TF_CPP_MIN_LOG_LEVEL": "4",
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
        "DIMA_REPLICA": str(idx),
    }
    if port is not None:
        env.update({
            "TPU_VISIBLE_CHIPS": str(idx),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        })
    return env


def make_shared_trace(n_requests: int, *, seed=0, vocab=256, n_templates=3,
                      template_len=28, suffix_len=4, max_news=(4, 16),
                      dup_frac=0.35):
    """Template-heavy request stream: every prompt is one of
    ``n_templates`` shared templates plus a short user suffix, and
    ``dup_frac`` of requests repeat a full earlier prompt verbatim —
    the shared-prefix / duplicate-prompt mix (few-shot headers, system
    prompts) the paged prefix registry exists for.  Returns (prompts,
    max_new)."""
    rng = np.random.default_rng(seed)
    templates = [rng.integers(0, vocab, template_len).astype(np.int32)
                 for _ in range(n_templates)]
    prompts, max_new = [], []
    for i in range(n_requests):
        if prompts and rng.random() < dup_frac:
            prompts.append(prompts[int(rng.integers(0, len(prompts)))].copy())
        else:
            t = templates[int(rng.integers(0, n_templates))]
            sfx = rng.integers(0, vocab, suffix_len).astype(np.int32)
            prompts.append(np.concatenate([t, sfx]))
        max_new.append(int(rng.integers(max_news[0], max_news[1] + 1)))
    return prompts, max_new


def _build_engine(spec: dict):
    """Construct the (reduced) model + ServeEngine a worker serves.
    Imported lazily: workers must set their env before jax loads."""
    import dataclasses

    import jax

    from repro.configs import RunConfig, get_arch, reduced
    from repro.inference import ServeEngine
    from repro.models import LM

    cfg = dataclasses.replace(reduced(get_arch(spec["arch"])),
                              dtype="float32")
    model = LM(cfg, RunConfig())
    params = model.init(jax.random.PRNGKey(spec["seed"]))
    eng = ServeEngine(model, params, bucket=spec["bucket"],
                      max_batch=spec["max_batch"], max_len=spec["max_len"],
                      kv=spec["kv"], block_size=spec["block_size"],
                      kv_blocks=spec["kv_blocks"])
    return model, params, eng


def _done_msg(idx: int, r) -> dict:
    return {"kind": "done", "replica": idx, "rid": r.rid,
            "out": [int(t) for t in r.out],
            "submitted_at": r.submitted_at, "done_at": r.done_at,
            "energy_pj": r.energy_pj}


def _worker(idx: int, spec: dict, req_q, res_conn, stop_evt, port=None):
    """Replica main: warm up, signal ready, then race the shared FIFO —
    pull whatever is visible, advance the engine one lockstep tick,
    repeat.  Runs until the parent sets ``stop_evt`` (it only does so
    once every request has reported done, so the queue is empty).

    Crash/preemption protocol: every pulled request is announced with a
    ``claim`` message BEFORE it enters the engine, so the parent knows
    exactly which rids die with a crashed replica and can reroute them.
    SIGTERM (a preemption, not a crash — fault_tolerance.PreemptionGuard)
    drains the seated slots to completion, hands queued-but-unseated
    rids back via ``requeue`` messages, and still emits the final stats
    record."""
    os.environ.update(replica_env(idx, port))
    import jax

    from repro.distributed.fault_tolerance import PreemptionGuard
    from repro.inference import Request
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    _, _, eng = _build_engine(spec)
    dev = jax.devices()[0]
    warm = Request(rid=-1, prompt=np.arange(1, 5, dtype=np.int32), max_new=3)
    eng.submit(warm)
    eng.run()
    warm_stats = dict(eng.stats)
    res_conn.send({"kind": "ready", "replica": idx})

    busy_s = 0.0
    preempted = False
    t_ready = time.time()
    with PreemptionGuard() as guard:
        while True:
            if guard.requested:
                preempted = True
                t0 = time.time()
                for r in eng.drain():            # finish in-flight slots
                    if r.rid >= 0:
                        res_conn.send(_done_msg(idx, r))
                busy_s += time.time() - t0
                for r in eng.queue:              # unseated: hand back
                    if r.rid >= 0:
                        res_conn.send({"kind": "requeue", "replica": idx,
                                   "rid": r.rid})
                break
            pulled = False
            # pull only what this replica can seat: hoarding beyond the
            # free slots would starve an idle peer racing the same FIFO
            while eng.free_slots > len(eng.queue):
                try:
                    rid, prompt, mx, t_sub = req_q.get_nowait()
                except queue_mod.Empty:
                    break
                res_conn.send({"kind": "claim", "replica": idx, "rid": rid})
                eng.submit(Request(rid=rid,
                                   prompt=np.asarray(prompt, np.int32),
                                   max_new=mx, submitted_at=t_sub))
                pulled = True
            if eng.busy:
                t0 = time.time()
                for r in eng.step():
                    if r.rid < 0:
                        continue
                    res_conn.send(_done_msg(idx, r))
                busy_s += time.time() - t0
            elif stop_evt.is_set():
                break
            elif not pulled:
                time.sleep(POLL_S)
    wall = max(time.time() - t_ready, 1e-9)
    res_conn.send({"kind": "stats", "replica": idx, "preempted": preempted,
               "platform": dev.platform, "device_kind": dev.device_kind,
               "utilization": round(busy_s / wall, 4),
               "busy_s": round(busy_s, 4), "wall_s": round(wall, 4),
               "jit_traces": dict(eng.jit_traces),
               "engine": {k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in eng.stats.items()},
               "warm": {k: warm_stats[k] for k in ("tokens", "steps")}})


def oracle_outputs(spec: dict, prompts, max_new) -> dict:
    """Sequential dense single-engine drain of the same requests — the
    token-identity reference for the fleet (greedy decode: same params,
    same prompts → same tokens, regardless of batching or replica)."""
    from repro.inference import Request

    spec = dict(spec, kv="dense")
    _, _, eng = _build_engine(spec)
    out = {}
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(rid=i, prompt=p.copy(), max_new=m))
        for r in eng.run():
            out[r.rid] = list(r.out)
    return out


WARM_RID = 10_000_000            # rids >= this mark warm-up traffic


def run_fleet(*, n_replicas=2, rate_rps=20.0, n_requests=48, arch="gemma3-1b",
              kv="paged", seed=0, max_batch=8, max_len=64, bucket=32,
              block_size=16, kv_blocks=None, slo_ms=2000.0, trace=None,
              check_tokens=False, mp_ctx="spawn", warm_passes=1,
              affinity=None, kill_after_done=None, kill_replica=None,
              respawn=False):
    """Launch ``n_replicas`` engine processes behind one FIFO, drive the
    open-loop Poisson trace through them, and return the fleet report.

    ``warm_passes`` full closed-loop drains of the same trace run first
    and are discarded, so the timed pass measures a steady-state server:
    every jit shape compiled and (paged) the prefix registry warm — the
    same protocol as bench_serving's same-engine warm drains.

    ``affinity="prompt"`` switches dispatch from the racing FIFO to
    prefix-affinity routing: each request goes to the replica picked by
    a stable hash of its prompt bytes, so an exact duplicate always
    lands on the replica whose prefix registry holds it (the per-replica
    registries are private — under FIFO racing a duplicate has a
    ``1/n_replicas`` chance of hitting the registry that saw the
    original).  Greedy tokens are routing-invariant, so the oracle check
    is unaffected.

    Crash handling: the dispatch loop polls every worker's
    ``is_alive()``; a replica that dies without its final stats record
    is a crash — its claimed-but-unfinished rids (and, under affinity,
    its queued private work) are rerouted to the survivors, counted in
    the report as ``replicas_crashed`` / ``requests_rerouted``, and with
    ``respawn=True`` a replacement worker is started in its slot.
    Rerouted requests keep their original ``submitted_at``, so the crash
    penalty shows up honestly in the latency percentiles.  Fault
    injection for tests/CI: ``kill_after_done=k`` SIGKILLs
    ``kill_replica`` (default the last) once ``k`` timed requests have
    completed — SIGKILL, not SIGTERM, because SIGTERM now means a
    graceful preemption drain."""
    spec = {"arch": arch, "kv": kv, "seed": seed, "max_batch": max_batch,
            "max_len": max_len, "bucket": bucket, "block_size": block_size,
            "kv_blocks": kv_blocks}
    prompts, max_new = trace if trace is not None else make_shared_trace(
        n_requests, seed=seed)
    n_requests = len(prompts)

    ports = [None] * n_replicas
    if tpu_fleet():
        chips = visible_tpu_chips()
        if n_replicas > chips:
            raise ValueError(f"{n_replicas} replicas need one TPU chip each; "
                             f"this host has {chips}")
        ports = [_free_port() for _ in range(n_replicas)]

    ctx = mp.get_context(mp_ctx)
    # one result pipe per worker process: a worker killed mid-send can
    # only break its own pipe, never a lock its peers write through
    conns = []
    stop_evt = ctx.Event()
    if affinity == "prompt":
        req_qs = [ctx.Queue() for _ in range(n_replicas)]
        home = [zlib.crc32(p.tobytes()) % n_replicas for p in prompts]
    elif affinity is None:
        shared = ctx.Queue()              # replicas race one FIFO
        req_qs = [shared] * n_replicas
        home = [0] * n_requests           # any queue IS the shared queue
    else:
        raise ValueError(f"affinity must be None or 'prompt', "
                         f"got {affinity!r}")

    def spawn(i):
        r, w = ctx.Pipe(duplex=False)
        p = ctx.Process(target=_worker, args=(i, spec, req_qs[i], w,
                                              stop_evt, ports[i]),
                        daemon=True)
        p.start()
        w.close()                         # the worker holds the write end
        conns.append(r)
        return p

    proc_by_idx = {i: spawn(i) for i in range(n_replicas)}
    kill_replica = (n_replicas - 1 if kill_replica is None
                    else int(kill_replica))

    results, stats = {}, {}
    ready = set()
    claimed = {}                          # rid -> replica that pulled it
    done_rids = set()
    dead = set()                          # crashed replica indices
    submit_t = {}                         # rid -> original submission time
    counters = {"replicas_crashed": 0, "requests_rerouted": 0}
    kill_state = {"armed": kill_after_done is not None}

    def item_for(rid):
        j = rid if rid < WARM_RID else (rid - WARM_RID) % n_requests
        return (rid, prompts[j].tolist(), int(max_new[j]),
                submit_t.get(rid, time.time()))

    def put_item(item, avoid=()):
        """Queue one request: the shared FIFO under racing dispatch, a
        surviving replica's private queue under affinity."""
        if affinity == "prompt":
            surv = [i for i in proc_by_idx if i not in dead
                    and i not in avoid] or [i for i in proc_by_idx
                                            if i not in dead]
            if not surv:
                raise RuntimeError("all replicas crashed; nothing left to "
                                   "reroute to")
            req_qs[surv[item[0] % len(surv)]].put(item)
        else:
            req_qs[0].put(item)

    def reroute(i):
        """A replica died mid-run: requeue its claimed-but-unfinished
        work (plus its private queue under affinity) on the survivors."""
        pending = [item_for(rid) for rid, r in claimed.items()
                   if r == i and rid not in done_rids]
        if affinity == "prompt":
            while True:
                try:
                    pending.append(req_qs[i].get_nowait())
                except queue_mod.Empty:
                    break
        for item in pending:
            put_item(item, avoid=(i,))
            counters["requests_rerouted"] += 1

    def handle(msg):
        kind = msg["kind"]
        if kind == "ready":
            ready.add(msg["replica"])
        elif kind == "claim":
            claimed[msg["rid"]] = msg["replica"]
        elif kind == "requeue":           # preempted worker handing back
            if msg["rid"] not in done_rids:
                put_item(item_for(msg["rid"]), avoid=(msg["replica"],))
                counters["requests_rerouted"] += 1
        elif kind == "done":
            done_rids.add(msg["rid"])
            if msg["rid"] < WARM_RID:
                results[msg["rid"]] = msg
        elif kind == "stats":
            stats[msg["replica"]] = msg

    def check_crashes():
        """The liveness poll: any worker that is gone without having
        delivered its stats record crashed — reroute its work, count it,
        optionally respawn a replacement in its slot."""
        for i, p in list(proc_by_idx.items()):
            if i in dead or p.is_alive() or i in stats:
                continue
            if i not in ready:
                # a replica that cannot start is a broken deployment
                # (no chip, bad environment), not a crash to survive
                raise RuntimeError(f"replica {i} exited during start-up "
                                   f"(exit code {p.exitcode})")
            dead.add(i)
            counters["replicas_crashed"] += 1
            reroute(i)
            if respawn:
                proc_by_idx[i] = spawn(i)
                dead.discard(i)           # replacement owns the slot again

    def maybe_kill():
        if (kill_state["armed"] and kill_replica not in dead
                and len(results) >= kill_after_done):
            kill_state["armed"] = False
            p = proc_by_idx.get(kill_replica)
            if p is not None and p.is_alive():
                p.kill()

    def receive(timeout):
        """Handle every message that arrives within ``timeout``; a
        closed pipe (its worker exited) is dropped.  Returns the count."""
        n = 0
        for c in mp_connection.wait(conns, timeout):
            try:
                msg = c.recv()
            except EOFError:
                conns.remove(c)
                continue
            handle(msg)
            n += 1
        return n

    def pump(timeout=READY_TIMEOUT_S):
        """Receive at least one message, polling worker liveness while
        waiting — a crash mid-run surfaces here as a reroute, not a
        hang."""
        deadline = time.time() + timeout
        while not receive(0.05):
            check_crashes()
            if time.time() > deadline:
                raise RuntimeError(
                    f"fleet stalled: no worker messages for "
                    f"{timeout:.0f}s ({len(results)}/{n_requests} done, "
                    f"crashed={sorted(dead)})")

    t0 = time.time()
    try:
        while len(ready) < len([i for i in proc_by_idx if i not in dead]):
            pump()

        for w in range(warm_passes):         # discarded steady-state warm
            base = WARM_RID + w * n_requests
            for i in range(n_requests):
                submit_t[base + i] = time.time()
                req_qs[home[i]].put((base + i, prompts[i].tolist(),
                                     int(max_new[i]), submit_t[base + i]))
            while len(done_rids & set(range(base, base + n_requests))) \
                    < n_requests:
                pump()

        rng = np.random.default_rng(seed + 1)
        arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n_requests))
        t0 = time.time()
        for i in range(n_requests):
            while True:                       # pace the open-loop clock
                lag = t0 + arrivals[i] - time.time()
                if lag <= 0:
                    break
                time.sleep(min(lag, 0.005))
                while receive(0):             # keep draining while pacing
                    pass
                check_crashes()
                maybe_kill()
            submit_t[i] = time.time()
            req_qs[home[i]].put((i, prompts[i].tolist(), int(max_new[i]),
                                 submit_t[i]))
        while len(results) < n_requests:
            pump()
            maybe_kill()
        stop_evt.set()
        while any(i not in stats for i in proc_by_idx if i not in dead):
            pump()
    finally:
        stop_evt.set()
        for p in proc_by_idx.values():
            p.join(timeout=60)
        for p in proc_by_idx.values():
            if p.is_alive():             # never exit holding a chip
                p.kill()
                p.join()

    lat = np.array([results[i]["done_at"] - results[i]["submitted_at"]
                    for i in range(n_requests)])
    last_done = max(results[i]["done_at"] for i in range(n_requests))
    tokens = sum(len(results[i]["out"]) for i in range(n_requests))
    wall = max(last_done - t0, 1e-9)
    per_replica = {}
    for i in sorted(stats):
        s = stats[i]
        per_replica[f"replica_{i}"] = {
            "requests": sum(1 for r in results.values()
                            if r["replica"] == i),
            "utilization": s["utilization"],
            "jit_traces": s["jit_traces"], "engine": s["engine"]}
    rec = {
        "replicas": n_replicas, "kv": kv,
        "platform": stats[min(stats)]["platform"] if stats else None,
        "device_kind": stats[min(stats)]["device_kind"] if stats else None,
        "dispatch": affinity or "fifo",
        "rate_rps": round(float(rate_rps), 3), "requests": n_requests,
        "tokens": tokens, "wall_s": round(wall, 4),
        "fleet_tokens_per_s": round(tokens / wall, 2),
        "latency_p50_s": round(float(np.percentile(lat, 50)), 4),
        "latency_p99_s": round(float(np.percentile(lat, 99)), 4),
        "slo_ms": slo_ms,
        "slo_attainment": round(float(np.mean(lat <= slo_ms / 1e3)), 4),
        "replicas_crashed": counters["replicas_crashed"],
        "requests_rerouted": counters["requests_rerouted"],
        "per_replica": per_replica,
    }
    if check_tokens:
        want = oracle_outputs(spec, prompts, max_new)
        got = {i: results[i]["out"] for i in range(n_requests)}
        if got != want:
            bad = sorted(i for i in want if got.get(i) != want[i])
            raise RuntimeError(
                f"fleet tokens diverged from the sequential dense oracle "
                f"on requests {bad[:8]} — greedy decode must be replica- "
                f"and paging-invariant")
        rec["token_identity"] = "ok"
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="open-loop Poisson arrival rate (requests/s)")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--kv", default="paged",
                    choices=["auto", "paged", "dense"])
    ap.add_argument("--affinity", default=None,
                    choices=["prompt"],
                    help="route requests to replicas by prompt hash "
                         "(duplicates hit the owning prefix registry) "
                         "instead of racing one FIFO")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--bucket", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--kv-blocks", type=int, default=None)
    ap.add_argument("--slo-ms", type=float, default=2000.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-tokens", action="store_true",
                    help="assert fleet tokens == sequential dense oracle")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: 2 tiny replicas, 10 requests, token-"
                         "identity assert vs the dense oracle")
    ap.add_argument("--smoke-fault", action="store_true",
                    help="CI fault-injection gate: the 2-replica paged "
                         "smoke with one replica SIGKILLed mid-trace — "
                         "asserts completion, token identity, and "
                         "replicas_crashed == 1")
    ap.add_argument("--respawn", action="store_true",
                    help="start a replacement worker in a crashed "
                         "replica's slot")
    ap.add_argument("--kill-after-done", type=int, default=None,
                    help="fault injection: SIGKILL --kill-replica once "
                         "this many timed requests completed")
    ap.add_argument("--kill-replica", type=int, default=None)
    args = ap.parse_args(argv)
    if args.smoke or args.smoke_fault:
        rec = run_fleet(n_replicas=2, rate_rps=10.0, n_requests=10,
                        arch=args.arch, kv=args.kv, seed=args.seed,
                        max_batch=4, max_len=64, bucket=32, block_size=16,
                        slo_ms=args.slo_ms, check_tokens=True,
                        trace=make_shared_trace(10, seed=args.seed,
                                                max_news=(2, 8)),
                        kill_after_done=(3 if args.smoke_fault else None),
                        respawn=args.respawn)
        if args.smoke_fault:
            assert rec["replicas_crashed"] == 1, rec
            assert rec["requests_rerouted"] >= 0, rec
    else:
        rec = run_fleet(n_replicas=args.replicas, rate_rps=args.rate,
                        n_requests=args.requests, arch=args.arch, kv=args.kv,
                        seed=args.seed, max_batch=args.max_batch,
                        max_len=args.max_len, bucket=args.bucket,
                        block_size=args.block_size, kv_blocks=args.kv_blocks,
                        slo_ms=args.slo_ms, check_tokens=args.check_tokens,
                        affinity=args.affinity, respawn=args.respawn,
                        kill_after_done=args.kill_after_done,
                        kill_replica=args.kill_replica)
    print(json.dumps(rec, indent=1))
    print(f"[replicas] {rec['replicas']}x {rec['kv']}: "
          f"{rec['fleet_tokens_per_s']} tok/s, p50 {rec['latency_p50_s']}s, "
          f"p99 {rec['latency_p99_s']}s, SLO {rec['slo_attainment']:.0%}"
          + (f", {rec['replicas_crashed']} crashed / "
             f"{rec['requests_rerouted']} rerouted"
             if rec["replicas_crashed"] else "")
          + (", token identity ok" if rec.get("token_identity") else ""))
    return rec


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
