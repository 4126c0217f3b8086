"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an XSpace (``*.xplane.pb``).  In it each chip is
a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line holds one event
per operation that ran on the chip and its ``XLA Modules`` line one
event per program execution.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) sit on the host
plane's thread lines.  All event times are nanoseconds on one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclass
class Summary:
    """Events of one traced window: per device, the operations and the
    program executions as (name, start_ns, end_ns); the benchmark's host
    spans likewise."""
    ops: list = field(default_factory=list)          # per device
    modules: list = field(default_factory=list)      # per device
    host_spans: list = field(default_factory=list)

    @property
    def n_devices(self) -> int:
        return len(self.ops)


def load(trace_dir: str):
    """The ProfileData of the one trace written under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {len(paths)}")
    return ProfileData.from_file(paths[0])


def summarize(profile) -> Summary:
    s = Summary()
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                dst = (ops if line.name == OPS_LINE
                       else mods if line.name == MODULES_LINE else None)
                if dst is not None:
                    dst.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            s.ops.append(sorted(ops, key=lambda e: e[1]))
            s.modules.append(sorted(mods, key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                s.host_spans.extend(
                    (e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    s.host_spans.sort(key=lambda e: e[1])
    return s


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_ns(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def busy_s(s: Summary) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not s.n_devices:
        return 0.0
    return sum(union_ns([(a, b) for _, a, b in ops])
               for ops in s.ops) / s.n_devices * 1e-9


def matching(events, patterns):
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in rx)]


def kernel_s(s: Summary, patterns) -> float:
    """Device seconds of the operations whose names match, averaged over
    the devices."""
    if not s.n_devices:
        return 0.0
    return sum(union_ns([(a, b) for _, a, b in matching(ops, patterns)])
               for ops in s.ops) / s.n_devices * 1e-9


def kernel_count(s: Summary, patterns) -> int:
    """Launches of the matching operations on the busiest device."""
    return max((len(matching(ops, patterns)) for ops in s.ops), default=0)


def executions(s: Summary) -> int:
    """Program executions on the busiest device."""
    return max((len(m) for m in s.modules), default=0)


_HLO = re.compile(r"^%?(\S+) = (\(?[a-z0-9]+\[[^\]]*\])")


def short_name(op: str) -> str:
    """An op event's name is its HLO instruction; keep the instruction's
    name and its (first) result shape: ``fusion.8 f32[32,1,128,2,128]``."""
    m = _HLO.match(op)
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m else op[:80]


def top_ops(s: Summary, k: int = 10):
    """[[op, device seconds]] of the k operations that took most time,
    summed over their launches on all devices, by ``short_name``."""
    tot = {}
    for ops in s.ops:
        for name, a, b in ops:
            n = short_name(name)
            tot[n] = tot.get(n, 0.0) + (b - a) * 1e-9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])][:k]


def idle_gaps(s: Summary, k: int = 10):
    """[[host span, seconds]]: the device's idle gaps inside the traced
    host work, each given to the ``bench.*`` span that overlaps it most
    (``idle`` where none does), summed by span and sorted."""
    if not s.n_devices or not s.host_spans:
        return []
    busy = union([(a, b) for _, a, b in s.ops[0]])
    lo = min(a for _, a, _ in s.host_spans)
    hi = max(b for _, _, b in s.host_spans)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = s.host_spans
    tot, j = {}, 0
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        # gaps come in time order and the spans do not nest, so spans
        # that end before this gap can end no later gap either
        while j < len(spans) and spans[j][2] <= a:
            j += 1
        best, over = "idle", 0.0
        for name, sa, sb in spans[j:]:
            if sa >= b:
                break
            o = min(b, sb) - max(a, sa)
            if o > over:
                best, over = name, o
        tot[best] = tot.get(best, 0.0) + (b - a) * 1e-9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])][:k]
