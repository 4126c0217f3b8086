import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (arch × shape × mesh) cell:  build the production mesh, lower
the right step (train/prefill/serve) against ShapeDtypeStruct inputs with
explicit in/out shardings, ``.compile()`` it, and record
``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs/bytes) and the
collective-op byte census parsed from the post-SPMD HLO — the §Roofline
inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json (cached).
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import RunConfig, cells, get_arch, get_shape
from repro.distributed.sharding import (ShardCtx, batch_shardings,
                                        cache_shardings, param_shardings)
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models import LM
from repro.optim import adamw_init

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
#: the chip the production meshes model (a 16x16 v5e pod, two for
#: multi-pod); benchmarks/roofline.py reads its peaks by this kind
TARGET_DEVICE_KIND = "TPU v5 lite"

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "c128": 16,
}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute", "collective-broadcast")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(tok):
    dt, dims = tok
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(.*\)\s*->")
_WHILE_RE = re.compile(
    r"while\(.*?condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:call|to_apply)=?\(?%?([\w\.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_OLD_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _split_computations(hlo_text: str):
    comps, cur, name = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP_HDR.match(line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            cur = []
            comps[name] = cur
            if line.lstrip().startswith("ENTRY"):
                comps["__entry__"] = cur
        elif cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                cur.append(line)
    return comps


def _line_collective(line):
    """(op, result_bytes, group_size) or None."""
    for op in _COLL_OPS:
        idx = line.find(f" {op}(")
        if idx < 0:
            idx = line.find(f" {op}-start(")
        if idx < 0:
            continue
        eq = line.find(" = ")
        if eq < 0 or eq > idx:
            return None
        toks = _SHAPE_RE.findall(line[eq:idx])
        rbytes = sum(_shape_bytes(t) for t in toks)
        if f"{op}-start(" in line:
            rbytes //= 2  # start ops repeat the shape in the result tuple
        m = _GROUPS_RE.search(line)
        if m:
            gsize = int(m.group(2))
        else:
            m = _GROUPS_OLD_RE.search(line)
            gsize = len(m.group(1).split(",")) if m else 1
        return op, rbytes, max(gsize, 1)
    return None


def collective_census(hlo_text: str) -> dict:
    """Per-device collective bytes, with while-loop bodies multiplied by
    their known trip counts (scan-over-layers!).

    operand_bytes follows the assignment's convention (sum of operand
    sizes); link_bytes is a ring-algorithm estimate of bytes/device
    actually crossing links.
    """
    comps = _split_computations(hlo_text)

    # per-computation direct tallies + sub-calls
    direct, calls = {}, {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        d = []
        c = []
        for line in lines:
            lc = _line_collective(line)
            if lc:
                d.append(lc)
            wm = _WHILE_RE.search(line)
            if wm:
                tm = _TRIP_RE.search(line)
                trip = int(tm.group(1)) if tm else 1
                c.append((wm.group(2), trip))
                c.append((wm.group(1), trip + 1))
            elif " call(" in line or " conditional(" in line:
                for cal in _CALL_RE.findall(line):
                    if cal in comps:
                        c.append((cal, 1))
        direct[name] = d
        calls[name] = c

    entry_name = next((n for n, l in comps.items()
                       if n != "__entry__" and l is comps.get("__entry__")),
                      None)

    mult = {}

    def visit(name, m, depth=0):
        if depth > 50 or name not in direct:
            return
        mult[name] = mult.get(name, 0) + m
        for callee, trip in calls.get(name, ()):
            visit(callee, m * trip, depth + 1)

    if entry_name:
        visit(entry_name, 1)
    else:  # fallback: count everything once
        for n in direct:
            mult[n] = 1

    out = {op: {"count": 0, "operand_bytes": 0, "link_bytes": 0}
           for op in _COLL_OPS}
    for name, items in direct.items():
        m = mult.get(name, 0)
        if m == 0:
            continue
        for op, rbytes, g in items:
            o = out[op]
            o["count"] += m
            if op == "all-gather":
                operand = rbytes // g
                link = rbytes * (g - 1) // g
            elif op == "reduce-scatter":
                operand = rbytes * g
                link = rbytes * (g - 1)
            elif op == "all-reduce":
                operand = rbytes
                link = 2 * rbytes * (g - 1) // g
            else:  # all-to-all, permutes
                operand = rbytes
                link = rbytes * (g - 1) // g if g > 1 else rbytes
            o["operand_bytes"] += m * operand
            o["link_bytes"] += m * link
    out["total_bytes"] = sum(v["operand_bytes"] for v in out.values()
                             if isinstance(v, dict))
    out["total_link_bytes"] = sum(v["link_bytes"] for v in out.values()
                                  if isinstance(v, dict))
    return out


def _lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
                quant: str = "none", variant: str = "baseline",
                remat: str = "nothing", kv: str = "bf16"):
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = ShardCtx(mesh, variant=variant)
    run = RunConfig(quant_mode=quant, remat_policy=remat, kv_dtype=kv)
    model = LM(cfg, run, ctx)

    batch_sp = specs_mod.input_specs(cfg, shape)
    batch_sh = batch_shardings(batch_sp, ctx)

    if shape.mode == "train":
        p_sp = specs_mod.param_specs(model)
        p_sh = param_shardings(p_sp, ctx)
        o_sp = jax.eval_shape(adamw_init, p_sp)
        # moments follow params; step is replicated. ZeRO-1 variant shards
        # the moments over 'data' instead (no_tp pairs with it).
        if variant == "no_tp":
            from repro.distributed.sharding import zero1_opt_shardings
            m_sh = zero1_opt_shardings(p_sp, ctx)
        else:
            m_sh = p_sh
        o_sh = {
            "m": m_sh,
            "v": m_sh,
            "step": ctx.named(jax.sharding.PartitionSpec()),
        }
        step = make_train_step(model, run)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, batch_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        with mesh:
            lowered = jitted.lower(p_sp, o_sp, batch_sp)
    else:
        p_sp = specs_mod.param_specs_bf16(model)
        if quant in ("dima", "dima4"):
            from repro.quant import quantize_params
            bits = 4 if quant == "dima4" else 8
            p_sp = jax.eval_shape(
                lambda p: quantize_params(p, bits=bits), p_sp)
        p_sh = param_shardings(p_sp, ctx)
        c_sp = specs_mod.cache_specs(model, shape)
        c_sh = cache_shardings(c_sp, ctx)
        if shape.mode == "prefill":
            step = make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, batch_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))
            with mesh:
                lowered = jitted.lower(p_sp, c_sp, batch_sp)
        else:
            step = make_decode_step(model)
            pos_sp = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, batch_sh, None),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))
            with mesh:
                lowered = jitted.lower(p_sp, c_sp, batch_sp, pos_sp)
    return cfg, shape, mesh, lowered


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             quant: str = "none", out_dir: Path = OUT_DIR,
             tag: str = "", variant: str = "baseline",
             remat: str = "nothing", kv: str = "bf16") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "quant": quant, "variant": variant, "ok": False,
        "device_kind": TARGET_DEVICE_KIND,
    }
    t0 = time.time()
    try:
        cfg, shape, mesh, lowered = _lower_cell(
            arch_name, shape_name, multi_pod, quant, variant, remat, kv)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        ca = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {
            k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "optimal_seconds",
             "bytes accessed output", "utilization operand 0")
        }
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                attr: int(getattr(ma, attr))
                for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                             "temp_size_in_bytes", "generated_code_size_in_bytes",
                             "alias_size_in_bytes")
                if hasattr(ma, attr)
            }
        except Exception as e:  # pragma: no cover - backend dependent
            rec["memory_analysis"] = {"error": str(e)}

        hlo = compiled.as_text()
        rec["hlo_bytes"] = len(hlo)
        from repro.launch.hlo_cost import analyze_hlo
        cost = analyze_hlo(hlo)
        rec["collectives"] = cost["collectives"]
        rec["hlo_cost"] = {
            "flops": cost["flops"],
            "bytes": cost["bytes"],
            "transcendental_elems": cost["transcendental_elems"],
        }
        rec["n_devices"] = mesh.devices.size
        rec["params"] = cfg.param_count()
        rec["active_params"] = cfg.active_param_count()
        rec["tokens_per_step"] = shape.tokens_per_step
        rec["ok"] = True
        del compiled, lowered, hlo
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)

    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fn = out_dir / f"{arch_name}__{shape_name}__{mesh_name}{suffix}.json"
    fn.write_text(json.dumps(rec, indent=1))
    status = "OK" if rec["ok"] else f"FAIL: {rec.get('error')}"
    print(f"[dryrun] {arch_name} x {shape_name} x {mesh_name}"
          f"{' ' + tag if tag else ''}: {status} ({rec['total_s']}s)",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant", default="none")
    ap.add_argument("--variant", default="baseline",
                    help="sharding variant: baseline|wg_ffn|no_tp|fsdp|xlstm_bshard")
    ap.add_argument("--remat", default="nothing",
                    help="remat policy: nothing|dots|everything")
    ap.add_argument("--kv", default="bf16", help="KV cache dtype: bf16|int8")
    ap.add_argument("--tag", default="", help="suffix for experiment variants")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out-dir", type=Path, default=OUT_DIR,
                    help="where the per-cell JSON records are written")
    args = ap.parse_args()

    if args.list:
        for a, s in cells():
            print(f"{a} {s}")
        return

    todo = []
    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for a, s in cells():
            for mp in meshes:
                todo.append((a, s, mp))
    else:
        todo.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for a, s, mp in todo:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        suffix = f"__{args.tag}" if args.tag else ""
        fn = args.out_dir / f"{a}__{s}__{mesh_name}{suffix}.json"
        if fn.exists() and not args.force:
            rec = json.loads(fn.read_text())
            if rec.get("ok"):
                print(f"[dryrun] {a} x {s} x {mesh_name}: cached OK")
                continue
        rec = run_cell(a, s, mp, quant=args.quant, out_dir=args.out_dir,
                       tag=args.tag,
                       variant=args.variant, remat=args.remat, kv=args.kv)
        failures += 0 if rec["ok"] else 1
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
