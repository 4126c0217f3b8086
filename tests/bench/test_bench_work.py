"""The work counts and the peak table the roofline shares divide by."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import work  # noqa: E402

V5E = "TPU v5 lite"


@pytest.mark.parametrize("B,ops,nbytes", [
    # 2*B*M*N; stored M*N + queries B*N + one 8-b code per (query, row)
    (256, 268_435_456, 524_288 + 65_536 + 524_288),
    (1, 1_048_576, 524_288 + 256 + 2_048),
])
def test_flagship_counts(B, ops, nbytes):
    # 32 banks of 64 stored 256-dim vectors
    assert work.ops(B, 2048, 256) == ops
    assert work.bytes_moved(B, 2048, 256) == nbytes


@pytest.mark.parametrize("shape,bound", [
    ((256, 2048, 256), "bytes"),    # the flagship batch: 8-b codes out
    ((1, 2048, 256), "bytes"),
    ((1024, 1024, 1024), "ops"),    # past the v5e's 480 ops per byte
])
def test_least_time_is_the_larger_bound(shape, bound):
    pk = work.peaks(V5E)
    t_ops = work.ops(*shape) / pk["int8_ops"]
    t_bytes = work.bytes_moved(*shape) / pk["hbm_bytes_per_s"]
    assert work.min_seconds(*shape, V5E) == max(t_ops, t_bytes)
    assert (t_ops > t_bytes) == (bound == "ops")


def test_v5e_peaks_are_the_published_ones():
    assert work.peaks(V5E) == {"bf16_flops": 197e12, "int8_ops": 393e12,
                               "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_roofline_share_reaches_100_only_at_the_least_time():
    t = work.min_seconds(256, 2048, 256, V5E)
    assert work.roofline_pct(3, 256, 2048, 256, V5E, 3 * t) == \
        pytest.approx(100.0)
    assert work.roofline_pct(3, 256, 2048, 256, V5E, 30 * t) == \
        pytest.approx(10.0)
    assert work.roofline_pct(0, 256, 2048, 256, V5E, t) is None
    assert work.roofline_pct(3, 256, 2048, 256, V5E, 0.0) is None


def test_mfu_of_one_call_per_second():
    assert work.mfu_pct(1.0, 256, 2048, 256, V5E) == pytest.approx(
        100.0 * 2 * 256 * 2048 * 256 / 393e12)
    assert work.mfu_pct(0.0, 256, 2048, 256, V5E) is None
