"""The program's spans and scopes read from a profiler trace
(bench/program_trace.py) and the per-layer metrics that read them, on a
small synthetic XSpace laid out as a TPU writes it: an operation's
``op_name`` is the ``tf_op`` stat of its event metadata."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench import program_trace as pt  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

KERNEL = "%dima_md_bank_batch.1 = (s32[4,2,1,128]) custom-call(u8[4])"
SCOPES = {
    1: ("%fusion.14 = f32[4,2,128,2,128] fusion(u32[4])",
        "jit(_bank_call_md)/dima.noise/vmap(vmap())/mul"),
    2: ("%select_multiply_fusion = f32[4,2,128,2] fusion(u32[4])",
        None),                       # its op_name is an interned ref
    3: (KERNEL, "jit(_bank_call_md)/dima.kernel/jit(dima_md_bank_batch)/"
                "pallas_call"),
    4: ("%copy.1 = s32[4,128] copy(s32[4,128] %bitcast.1)", ""),
    5: ("%top_k.1 = s32[2,5] custom-call(s32[2,64])", "jit(decide)/top_k"),
}
NOISE_REF = "jit(_bank_call_md)/dima.noise/vmap(vmap())/select_n"


def _event(meta, start_ns, dur_ns, stats=""):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} {stats} }}")


def _xspace(program=True, shift=0):
    """One device: noise 100-400 and 300-500, kernel 600-1000, a copy
    1200-1300 and the decision 1600-1700 (busy 1000 ns).  Host:
    bench.call 0-1500 holding dima.call 50-1450, which holds dima.prep
    50-600 (device idle 50-100 and 500-600), dima.launch 600-650 and
    dima.assemble 650-1450 (idle 1000-1200 and 1300-1450); bench.decide
    1500-1550, bench.fetch 1550-1800.  ``program=False`` is the trace of
    a program without spans or scopes; ``shift`` moves the device ops."""
    ops = " ".join(_event(k, a + shift, n) for k, a, n in [
        (1, 100, 300), (2, 300, 200), (3, 600, 400), (4, 1200, 100),
        (5, 1600, 100)])
    md = []
    for k, (name, op) in SCOPES.items():
        stat = ""
        if program and op:
            stat = f'stats {{ metadata_id: 9 str_value: "{op}" }}'
        elif program and op is None:
            stat = "stats { metadata_id: 9 ref_value: 10 }"
        md.append(f'event_metadata {{ key: {k} value {{ id: {k} '
                  f'name: "{name}" {stat} }} }}')
    call_stats = ('stats { metadata_id: 1 str_value: "matmat" } '
                  'stats { metadata_id: 2 int64_value: 2 }')
    host = [_event(1, 0, 1500), _event(2, 1500, 50), _event(3, 1550, 250)]
    if program:
        host += [_event(4, 50, 1400, call_stats), _event(5, 50, 550),
                 _event(6, 600, 50), _event(7, 650, 800)]
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_event(6, 100, 900)} }}
  {" ".join(md)}
  event_metadata {{ key: 6 value {{ id: 6 name: "jit__bank_call_md(1)" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
  stat_metadata {{ key: 10 value {{ id: 10 name: "{NOISE_REF}" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {" ".join(host)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.call" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.decide" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.fetch" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "dima.call" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "dima.prep" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "dima.launch" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "dima.assemble" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "kind" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "B" }} }}
}}
"""


def _write(root: Path, name: str, program=True, shift=0):
    """The trace where the harness would write it: (summary, path)."""
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(
        _xspace(program, shift))
    path = root / name / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    return tr.summarize(ProfileData.from_file(str(path))), path


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    root = tmp_path_factory.mktemp("traces")
    s, _ = _write(root, "bench-trace-a")
    return root, s, pt.find(s, root=str(root))


def _ctx(s):
    return {"summary": s, "busy_s": tr.busy_s(s), "window_s": 1800e-9,
            "calls": 1}


def test_ops_carry_their_op_name(run):
    _, s, t = run
    assert len(t.ops) == 1
    assert [n for n, *_ in t.ops[0]] == [SCOPES[1][1], NOISE_REF,
                                         SCOPES[3][1], "", SCOPES[5][1]]
    # the same events, on the same clock, as the summary's
    assert [e[1:] for e in t.ops[0]] == [e[1:] for e in s.ops[0]]


def test_program_spans_in_time_order(run):
    _, _, t = run
    assert [n for n, *_ in t.spans] == ["dima.call", "dima.prep",
                                        "dima.launch", "dima.assemble"]


@pytest.mark.parametrize("scope,seconds", [
    ("dima.noise", 400e-9),          # union of 100-400 and 300-500
    ("dima.kernel", 400e-9),
    ("jit(decide)", 100e-9),
    ("dima", 0.0),                   # a whole component, not a prefix
    ("dima.call", 0.0),              # host spans are not device scopes
])
def test_scope_time_is_the_union_of_its_ops(run, scope, seconds):
    _, _, t = run
    assert pt.scope_s(t, scope) == pytest.approx(seconds)


@pytest.mark.parametrize("names,seconds", [
    (pt.GLUE, 1350e-9),
    (("dima.launch",), 50e-9),
    (("dima.call",), 1400e-9),
    (("bench.call",), 0.0),          # the benchmark's spans are not read
])
def test_span_time(run, names, seconds):
    _, _, t = run
    assert pt.span_s(t, names) == pytest.approx(seconds)


@pytest.mark.parametrize("names,seconds", [
    (pt.GLUE, 500e-9),               # 150 in prep, 350 in assemble
    (("dima.prep",), 150e-9),
    (("dima.launch",), 0.0),
    (("dima.call",), 500e-9),        # the launch's 50 ns are busy
])
def test_device_idle_inside_spans(run, names, seconds):
    _, _, t = run
    assert pt.idle_in_spans_s(t, names) == pytest.approx(seconds)


def test_kernel_scope_equals_the_kernel_pattern(run):
    _, s, t = run
    assert pt.scope_s(t, "dima.kernel") == pytest.approx(
        tr.kernel_s(s, [r"^%dima_md_bank_batch(\.\d+)? = "]))


def test_idle_gaps_still_go_to_bench_spans_only(run):
    # gaps 0-100, 500-600 and 1000-1200 in bench.call, 1300-1600 mostly
    # in it, 1700-1800 in bench.fetch: the dima.* spans inside take none
    _, s, _ = run
    assert dict(tr.idle_gaps(s)) == pytest.approx({"bench.call": 700e-9,
                                                   "bench.fetch": 100e-9})


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                               f"bench.metrics.{name}").read


READINGS = {
    "noise_busy_share.batch": 40.0,          # 400 of 1000 busy ns
    "kernel_busy_share.batch": 40.0,
    "glue_idle_share.batch": 100.0 * 500 / 1800,
    "glue_idle_share.single": 100.0 * 500 / 1800,
    "glue_host_ms.single": 1350e-6,          # one call
    "launch_host_ms.single": 50e-6,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_metric_readers(run, monkeypatch, name):
    root, s, _ = run
    monkeypatch.setattr(pt.tempfile, "gettempdir", lambda: str(root))
    assert _reader(name)(_ctx(s)) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_a_program_without_spans_reads_nothing(tmp_path, monkeypatch, name):
    s, _ = _write(tmp_path, "bench-trace-parent", program=False)
    monkeypatch.setattr(pt.tempfile, "gettempdir", lambda: str(tmp_path))
    assert _reader(name)(_ctx(s)) is None


def test_find_takes_the_run_s_own_trace(tmp_path):
    s, path = _write(tmp_path, "bench-trace-mine")
    other, _ = _write(tmp_path, "bench-trace-newer", shift=7)
    os.utime(path, (1, 1))           # the other run's trace is newer
    assert pt.find(s, root=str(tmp_path)).ops[0][0][1] == 100.0
    assert pt.find(other, root=str(tmp_path)).ops[0][0][1] == 107.0
    (tmp_path / "bench-trace-torn" / "t.xplane.pb").parent.mkdir()
    (tmp_path / "bench-trace-torn" / "t.xplane.pb").write_bytes(b"\x0a\xff")
    assert pt.find(s, root=str(tmp_path)).ops[0][0][1] == 100.0
    s.ops[0].pop()                   # a summary of no trace there
    assert pt.find(s, root=str(tmp_path)) is None
    assert pt.find(other, root=str(tmp_path / "nowhere")) is None
