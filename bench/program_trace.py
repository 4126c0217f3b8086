"""The program's own spans and scopes in a profiler trace.

The program marks its work two ways (``core/api.py``, ``kernels/ops.py``):

- host spans (``jax.profiler.TraceAnnotation``) named ``dima.call`` and,
  inside it, ``dima.prep``, ``dima.launch`` and ``dima.assemble``;
- device scopes (``jax.named_scope``) ``dima.noise`` and ``dima.kernel``
  inside the jitted banked bodies, which XLA keeps in each operation's
  ``op_name``.

On a TPU the profiler keeps an operation's ``op_name`` as the ``tf_op``
stat of the operation's event metadata, which ``jax.profiler.ProfileData``
does not expose, so this module reads the XSpace itself through the few
fields of its schema it needs.  ``bench/harness.py`` hands a metric reader
the trace's summary but not its file, so ``find`` looks for the run's
trace where the harness writes it and checks that it is the one the
summary was made from.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile
from dataclasses import dataclass, field

from bench import trace_reduce

SPAN_PREFIX = "dima."
#: the host work before and after the one fused launch
GLUE = ("dima.prep", "dima.assemble")
OP_NAME_STAT = "tf_op"


@dataclass
class Trace:
    """Per device, the operations as (op_name, start_ns, end_ns), in
    device order and by start (op_name "" where the event carries none);
    the program's ``dima.*`` host spans as (name, start_ns, end_ns)."""
    ops: list = field(default_factory=list)
    spans: list = field(default_factory=list)


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """A message class for the XSpace fields read here (tsl's
    ``xplane.proto``; a map is a repeated key/value entry on the wire)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msgs = {
        "XSpace": [("planes", 1, many, "XPlane")],
        "XPlane": [("name", 2, one, F.TYPE_STRING),
                   ("lines", 3, many, "XLine"),
                   ("event_metadata", 4, many, "EventMetadataEntry"),
                   ("stat_metadata", 5, many, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                               ("value", 2, one, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                              ("value", 2, one, "XStatMetadata")],
        "XLine": [("name", 2, one, F.TYPE_STRING),
                  ("timestamp_ns", 3, one, F.TYPE_INT64),
                  ("events", 4, many, "XEvent")],
        "XEvent": [("metadata_id", 1, one, F.TYPE_INT64),
                   ("offset_ps", 2, one, F.TYPE_INT64),
                   ("duration_ps", 3, one, F.TYPE_INT64)],
        "XEventMetadata": [("id", 1, one, F.TYPE_INT64),
                           ("name", 2, one, F.TYPE_STRING),
                           ("stats", 5, many, "XStat")],
        "XStatMetadata": [("id", 1, one, F.TYPE_INT64),
                          ("name", 2, one, F.TYPE_STRING)],
        "XStat": [("metadata_id", 1, one, F.TYPE_INT64),
                  ("str_value", 5, one, F.TYPE_STRING),
                  ("ref_value", 7, one, F.TYPE_UINT64)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_program_trace_xplane.proto",
        package="bench_program_trace", syntax="proto3")
    for name, fields in msgs.items():
        m = fd.message_type.add(name=name)
        for fname, number, label, typ in fields:
            f = m.field.add(name=fname, number=number, label=label)
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, \
                    f".bench_program_trace.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_program_trace.XSpace"))


def _times(line, ev):
    """(start_ns, end_ns) in whole nanoseconds, as ProfileData gives them
    to ``bench/trace_reduce.py``."""
    start = line.timestamp_ns + ev.offset_ps // 1000
    return float(start), float(start + ev.duration_ps // 1000)


def parse(raw: bytes) -> Trace:
    """The Trace of one serialized XSpace."""
    space = _xspace_class().FromString(raw)
    t = Trace()
    devices = []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            op_name = {}
            for k, md in meta.items():
                for st in md.stats:
                    if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                        op_name[k] = (st.str_value if st.str_value
                                      else stat_names.get(st.ref_value, ""))
            ops = [(op_name.get(ev.metadata_id, ""), *_times(line, ev))
                   for line in plane.lines
                   if line.name == trace_reduce.OPS_LINE
                   for ev in line.events]
            devices.append((int(plane.name.rsplit(":", 1)[1]),
                            sorted(ops, key=lambda e: e[1])))
        elif plane.name.startswith("/host:"):
            ours = {k: md.name for k, md in meta.items()
                    if md.name.startswith(SPAN_PREFIX)}
            t.spans.extend((ours[ev.metadata_id], *_times(line, ev))
                           for line in plane.lines for ev in line.events
                           if ev.metadata_id in ours)
    t.ops = [ops for _, ops in sorted(devices)]
    t.spans.sort(key=lambda e: e[1])
    return t


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float) -> Trace:
    with open(path, "rb") as f:
        return parse(f.read())


def _same_window(t: Trace, s) -> bool:
    """The Trace and the summary hold the same operations on device 0."""
    if not t.ops or not s.ops or len(t.ops[0]) != len(s.ops[0]):
        return False
    return all(a[1:] == b[1:] for a, b in zip(t.ops[0], s.ops[0]))


def find(s, root: str = None):
    """The Trace of the run whose summary is ``s``: the newest trace under
    ``root`` (where the harness makes its ``bench-trace-*`` directories)
    that holds the summary's operations; None where there is none."""
    from google.protobuf.message import DecodeError
    root = root or tempfile.gettempdir()
    found = []
    for path in glob.glob(os.path.join(root, "bench-trace-*", "**",
                                       "*.xplane.pb"), recursive=True):
        try:
            found.append((os.path.getmtime(path), path))
        except OSError:             # another run's, removed meanwhile
            continue
    for mtime, path in sorted(found, reverse=True):
        try:
            t = _read(path, mtime)
        except (OSError, DecodeError):  # removed, or still being written
            continue
        if _same_window(t, s):
            return t
    return None


def _has(op_name: str, scope: str) -> bool:
    return scope in op_name.split(":", 1)[0].split("/")


def scope_s(t: Trace, scope: str) -> float:
    """Device seconds of the operations whose ``op_name`` has ``scope`` as
    a path component (their union per device), averaged over devices."""
    if not t.ops:
        return 0.0
    return sum(trace_reduce.union_ns([(a, b) for n, a, b in ops
                                      if _has(n, scope)])
               for ops in t.ops) / len(t.ops) * 1e-9


def span_s(t: Trace, names) -> float:
    """Summed seconds of the program spans with these names."""
    return sum(b - a for n, a, b in t.spans if n in names) * 1e-9


def idle_in_spans_s(t: Trace, names) -> float:
    """Seconds inside the union of the named spans in which no operation
    ran on device 0."""
    spans = trace_reduce.union([(a, b) for n, a, b in t.spans
                                if n in names])
    if not spans or not t.ops:
        return 0.0
    busy = trace_reduce.union([(a, b) for _, a, b in t.ops[0]])
    covered, j = 0.0, 0
    for a, b in spans:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            covered += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
    return (sum(b - a for a, b in spans) - covered) * 1e-9
