"""Batched serving driver (prefill + decode) with optional DIMA-quantized
weights — the paper's inference technique as a serving feature.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --batch 4 --prompt-len 32 --gen 16 --quant dima --backend multibank

Requests route through ``inference.ServeEngine`` (continuous batching:
per-slot positions, vmapped per-row cache writes — docs/serving.md; the
legacy ``bucketed`` static path was retired after its one release of
fallback).  ``--kv`` selects the KV layout: ``paged`` (default under
``auto`` where supported) serves from the global block pool with prefix
reuse and copy-on-write, ``dense`` keeps the per-slot contiguous layout
(one release of bitwise-parity oracle); ``--block-size``/``--kv-blocks``
size the pool.  Frontend-embedding archs (``external_embed``) stay on
the static ``generate()`` path — the engine's slot table is token-id
based.

``--quant dima`` stores every matmul weight as sub-ranged offset-binary
uint8 (quant/subrange.py) and (with --dima-noise) injects the calibrated
analog noise model — the LM-scale version of Fig. 5's energy↔accuracy
knob.  ``--analog-lm`` goes further: the whole model is planned onto
DIMA banks, calibrated, and *executed* through the analog chain
(analog_lm/ — bank planner → calibration store → AnalogRouter), with
pJ/token accounted from the conversions each token actually runs.
Reports tokens/s and, for the DIMA paths, the modeled pJ/token
(core/energy.py + core/mapping.py).  ``--backend multibank`` prices
tokens through the bank-sharded substrate's amortized CTRL model
(``--n-banks`` overrides the paper's 32); the other analog backends use
the single-bank model and ``digital`` the conventional architecture.
``--precision B`` selects the ``bitserial`` substrate: every weight read
executes as B bit planes and each token is billed B plane conversions
per weight byte (B=1 is the paper-exact binary-word path).
``--temperature``/``--top-k`` switch the engine from greedy to per-slot
sampling (fold_in(key, slot) streams).

The engine drive runs under a ``PreemptionGuard``: SIGTERM/SIGINT stops
admission, drains the in-flight slots to completion
(``ServeEngine.drain``), and prints final per-request stats (tokens,
latency, energy) plus the rids left unserved — no mid-decode kill.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import dima as dima_api
from repro.configs import RunConfig, get_arch, reduced
from repro.core.params import DimaParams
from repro.distributed.fault_tolerance import PreemptionGuard
from repro.distributed.sharding import ShardCtx
from repro.inference import Request, ServeEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM
from repro.quant import DimaNoiseModel, quantize_params


def dima_energy_per_token(cfg, p: DimaParams = DimaParams(), backend=None,
                          n_banks=None, n_planes=1):
    """Modeled DIMA decode energy: every active weight byte is read once
    per token through MR-FR banks.  Routed through the unified backend
    API so the substrate is swappable — ``"multibank"`` amortizes the
    fixed CTRL energy over its banks (and, since the fused bank axis,
    also *executes* all banks in one dispatch), ``"bitserial"`` bills
    every read per plane (``n_planes``×), everything else prices
    single-bank (``"digital"``: the conventional architecture)."""
    kw = {}
    if backend == "multibank" and n_banks is not None:
        kw["n_banks"] = n_banks
    if backend == "bitserial":
        kw["n_planes"] = n_planes
    be = dima_api.get_backend(backend or "reference", p, **kw)
    return dima_api.weights_energy_per_token(cfg.active_param_count(), be)


def generate(model, params, tokens, gen_len, dima=None):
    B, S = tokens.shape
    cfg = model.cfg
    table = None
    if cfg.external_embed:
        # frontend stub: deterministic frame/patch embedding table
        table = jax.random.normal(jax.random.PRNGKey(17),
                                  (cfg.vocab_size, cfg.d_model),
                                  jnp.bfloat16)

    def emb(t):
        return None if table is None else table[t]

    cache = model.init_cache(B, S + gen_len)
    logits, cache = model.prefill(
        params, cache,
        tokens=None if cfg.external_embed else tokens,
        embeds=emb(tokens), dima=dima)
    out = [jnp.argmax(logits, -1).astype(jnp.int32)]
    step = jax.jit(lambda p, c, t, e, pos: model.decode_step(
        p, c, pos, tokens=t, embeds=e, dima=dima))
    for i in range(gen_len - 1):
        nxt = out[-1][:, None]
        lg, cache = step(params, cache,
                         None if cfg.external_embed else nxt,
                         emb(nxt), jnp.asarray(S + i, jnp.int32))
        out.append(jnp.argmax(lg, -1).astype(jnp.int32))
    return jnp.stack(out, axis=1)


def _make_backend(args):
    """The costing/execution backend the CLI selected: multibank takes
    --n-banks, bitserial takes --precision, the rest are bare."""
    kw = {}
    if args.n_banks is not None:
        kw["n_banks"] = args.n_banks
    if args.backend == "bitserial":
        kw["n_planes"] = args.precision
    return dima_api.get_backend(args.backend, **kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--quant", default="none", choices=["none", "dima", "dima4"])
    ap.add_argument("--dima-noise", action="store_true")
    ap.add_argument("--analog-lm", action="store_true",
                    help="plan + calibrate the model onto DIMA banks and "
                         "execute the forward through the analog chain "
                         "(implies --quant dima; --dima-noise samples the "
                         "conversion noise on the analog path)")
    ap.add_argument("--backend", default="reference",
                    choices=sorted(dima_api.BACKENDS),
                    help="DIMA substrate used for the energy model "
                         "(multibank = bank-sharded, amortized CTRL)")
    ap.add_argument("--n-banks", type=int, default=None,
                    help="bank count for --backend multibank "
                         "(default: the paper's 32-bank scenario)")
    ap.add_argument("--precision", type=int, default=1,
                    choices=[1, 2, 4, 8], metavar="B",
                    help="bit-serial plane count (B in {1,2,4,8}): selects "
                         "the bitserial substrate — weights execute as B "
                         "bit planes per read and every token is billed "
                         "B plane conversions per weight byte (1 = the "
                         "paper-exact binary-word path)")
    ap.add_argument("--kv", default="auto",
                    choices=["auto", "paged", "dense"],
                    help="KV-cache layout: paged = global block pool + "
                         "prefix reuse (docs/serving.md); auto picks paged "
                         "when the arch supports it")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block when --kv paged")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="block-pool size when --kv paged (default: enough "
                         "for max_batch full-length sequences)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-slot sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation when --temperature > 0")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.n_banks is not None and args.backend != "multibank":
        ap.error(f"--n-banks only applies to --backend multibank "
                 f"(got --backend {args.backend})")
    if args.precision != 1 and args.backend not in ("reference", "bitserial"):
        ap.error(f"--precision {args.precision} needs the bitserial "
                 f"substrate (got --backend {args.backend})")
    if args.precision != 1:
        args.backend = "bitserial"
    if args.analog_lm and args.quant == "dima4":
        ap.error("--analog-lm requires 8-bit records (--quant dima)")
    if args.analog_lm:
        args.quant = "dima"
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = LM(cfg, RunConfig(), ShardCtx(None))
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)

    dima = None
    if args.quant != "none":
        params = quantize_params(params, bits=4 if args.quant == "dima4" else 8)
        if args.analog_lm:
            if cfg.external_embed:
                ap.error("--analog-lm needs a token-id arch "
                         "(external_embed archs bypass the engine)")
            from repro.analog_lm import AnalogRouter, calibrate_model
            be = _make_backend(args)
            cal = np.asarray(jax.random.randint(
                jax.random.PRNGKey(args.seed + 2), (2, args.prompt_len),
                0, cfg.vocab_size), np.int32)
            t0 = time.time()
            store = calibrate_model(model, params, cal, backend=be)
            dima = AnalogRouter(cfg, params, store, backend=be,
                                noisy=args.dima_noise,
                                key=jax.random.PRNGKey(args.seed + 1))
            print(f"[serve] analog-lm: {dima.n_banks:,} banks, calibrated "
                  f"{cfg.n_layers} layers in {time.time()-t0:.1f}s, "
                  f"measured {dima.pj_per_token()/1e6:.2f} µJ/token "
                  f"({'noisy' if args.dima_noise else 'zero-noise'} chain)")
        else:
            if args.dima_noise:
                dima = DimaNoiseModel(key=jax.random.PRNGKey(args.seed + 1))
            pj, banks = dima_energy_per_token(cfg, DimaParams(), args.backend,
                                              args.n_banks, args.precision)
            if args.backend == "digital":   # bank-less conventional arch
                where = f"{cfg.active_param_count():,} weight bytes/token"
                amort = "conventional fetch-then-compute"
            elif args.backend == "multibank":
                nb = args.n_banks or DimaParams().n_banks_multibank
                where = f"{banks:,} SRAM banks"
                amort = f"multi-bank ×{nb}, amortized CTRL"
            elif args.backend == "bitserial":
                where = f"{banks:,} SRAM banks"
                amort = (f"bit-serial ×{args.precision} planes"
                         if args.precision != 1 else
                         "bit-serial, single 8-b plane")
            else:
                where = f"{banks:,} SRAM banks"
                amort = "single-bank"
            print(f"[serve] DIMA weights: {where}, modeled "
                  f"{pj/1e6:.2f} µJ/token ({args.backend} backend, {amort})")

    toks = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                              cfg.vocab_size)
    t0 = time.time()
    if cfg.external_embed:
        # frontend-embedding archs bypass the engine's token slot table
        out = generate(model, params, toks, args.gen, dima=dima)
    else:
        eng = ServeEngine(
            model, params, bucket=args.prompt_len, max_batch=args.batch,
            max_len=args.prompt_len + args.gen, dima=dima,
            kv=args.kv, block_size=args.block_size, kv_blocks=args.kv_blocks,
            backend=_make_backend(args),
            temperature=args.temperature, top_k=args.top_k,
            sample_key=jax.random.PRNGKey(args.seed + 3))
        prompts = np.asarray(toks, np.int32)
        for i in range(args.batch):
            eng.submit(Request(rid=i, prompt=prompts[i], max_new=args.gen))
        done, preempted = [], False
        with PreemptionGuard() as guard:
            while eng.busy:
                if guard.requested:      # SIGTERM/SIGINT: drain, don't admit
                    preempted = True
                    done.extend(eng.drain())
                    break
                done.extend(eng.step())
        done = sorted(done, key=lambda r: r.rid)
        if preempted:
            for r in done:
                print(f"[serve] drained rid={r.rid}: {len(r.out)} tokens, "
                      f"{r.done_at - r.submitted_at:.2f}s, "
                      f"{r.energy_pj/1e6:.2f} µJ")
            unserved = [r.rid for r in eng.queue]
            print(f"[serve] preempted: {len(done)} in-flight request(s) "
                  f"drained, {len(unserved)} left queued {unserved}")
            if not done:
                return None
            out = jnp.asarray(np.stack([r.out for r in done]))
        else:
            out = jnp.asarray(np.stack([r.out for r in done]))
    dt = time.time() - t0
    n_tok = out.shape[0] * args.gen
    print(f"[serve] generated {out.shape} in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    print("[serve] sample:", np.asarray(out[0][:12]))
    return out


if __name__ == "__main__":
    main()
