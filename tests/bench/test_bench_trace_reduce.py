"""The reduction from a profiler trace to busy, idle, kernel time and
program executions, on a small synthetic XSpace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, work  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

KERNEL = "%dima_md_bank_batch.1 = (s32[32,256,1,128]) custom-call(u8[32])"
KERNEL_DP = "%dima_dp_bank_batch.1 = (s32[32,256,1,128]) custom-call(u8[32])"
NOISE = "%fusion.8 = f32[32,256,128,2,128] fusion(u32[32])"
COPY = "%copy.1 = s32[32,128] copy(s32[32,128] %bitcast.1)"


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _xspace():
    """One device: noise 0-400 ns, copy 300-500 (overlaps), kernel
    600-1000, a second kernel 1500-1700; two program executions; host
    spans bench.call 0-1000, bench.fetch 1000-1500, bench.call
    1500-1800."""
    ops = " ".join([_event(1, 0, 400), _event(2, 300, 200),
                    _event(3, 600, 400), _event(3, 1500, 200)])
    mods = " ".join([_event(4, 0, 1000), _event(5, 1500, 200)])
    host = " ".join([_event(1, 0, 1000), _event(2, 1000, 500),
                     _event(1, 1500, 300), _event(3, 0, 1800)])
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mods} }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 0 {_event(2, 0, 1800)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{NOISE}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{COPY}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{KERNEL}" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit__bank_call_md(1)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_squeeze(2)" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.call" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.fetch" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "PjitFunction(f)" }} }}
}}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return tr.summarize(ProfileData.from_text_proto(_xspace()))


def test_device_events_and_host_spans(summary):
    assert summary.n_devices == 1
    assert len(summary.ops[0]) == 4          # the async line is not busy
    assert [n for n, *_ in summary.host_spans] == [
        "bench.call", "bench.fetch", "bench.call"]


def test_busy_is_the_union_of_operations(summary):
    # [0, 500] + [600, 1000] + [1500, 1700]
    assert tr.busy_s(summary) == pytest.approx(1100e-9)


@pytest.mark.parametrize("patterns,seconds,count", [
    ([r"^%dima_md_bank_batch(\.\d+)? = "], 600e-9, 2),
    ([r"^%fusion\.8 = "], 400e-9, 1),
    ([r"^%dima_dp_bank_batch(\.\d+)? = "], 0.0, 0),
])
def test_kernel_time_by_name(summary, patterns, seconds, count):
    assert tr.kernel_s(summary, patterns) == pytest.approx(seconds)
    assert len(tr.matching(summary.ops[0], patterns)) == count


def test_calls_are_the_bench_call_spans(summary):
    assert tr.calls(summary) == 2


def _calls(launches, kernel):
    """Three calls 1000 ns apart; in each, 100 ns of noise, then
    ``launches`` launches of ``kernel`` that take 400 ns together."""
    ops, spans, d = [], [], 400 // launches
    for t in (0, 1000, 2000):
        spans.append(("bench.call", t, t + 900))
        ops.append((NOISE, t, t + 100))
        ops += [(kernel, t + 100 + i * d, t + 100 + (i + 1) * d)
                for i in range(launches)]
    return tr.Summary(ops=[ops], modules=[[]], host_spans=spans)


@pytest.mark.parametrize("launches", [1, 4])
@pytest.mark.parametrize("metric,kernel", [("dima_md_roofline", KERNEL),
                                           ("dima_dp_roofline", KERNEL_DP)],
                         ids=["md", "dp"])
def test_roofline_counts_calls_not_launches(metric, kernel, launches):
    """A kernel launched several times a call reads the share that one
    launch a call of the same summed time reads: 3 calls' least time
    over 1200 ns."""
    reader = harness.load_module(ROOT / "bench" / "metrics" / f"{metric}.py",
                                 f"bench.metrics.{metric}")
    ctx = {"B": 256, "M": 2048, "N": 256, "device_kind": "TPU v5 lite"}
    got = reader.read({**ctx, "summary": _calls(launches, kernel)})
    one = reader.read({**ctx, "summary": _calls(1, kernel)})
    assert got == pytest.approx(one)
    assert one == pytest.approx(
        100 * 3 * work.min_seconds(256, 2048, 256, "TPU v5 lite") / 1200e-9)


def test_program_executions(summary):
    assert tr.executions(summary) == 2


def test_top_ops_by_short_name(summary):
    top = tr.top_ops(summary)
    assert [n for n, _ in top] == ["dima_md_bank_batch.1 s32[32,256,1,128]",
                                   "fusion.8 f32[32,256,128,2,128]",
                                   "copy.1 s32[32,128]"]
    assert top[0][1] == pytest.approx(600e-9)


def test_idle_gaps_go_to_the_overlapping_host_span(summary):
    # gaps: 500-600 (bench.call), 1000-1500 (bench.fetch),
    # 1700-1800 (bench.call)
    gaps = dict(tr.idle_gaps(summary))
    assert gaps == pytest.approx({"bench.fetch": 500e-9,
                                  "bench.call": 200e-9})


def test_load_wants_exactly_one_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.load(str(tmp_path))
