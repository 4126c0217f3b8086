"""Multi-replica serving tier: trace construction, fleet smoke (2 spawn
processes behind one FIFO), and token identity vs the dense oracle.

The fleet smoke is marked slow (two process spawns, each compiling its
own engine); CI additionally runs ``python -m repro.launch.replicas
--smoke`` as a dedicated step, which is the same path with the
token-identity assert enabled.
"""
import numpy as np
import pytest

from repro.launch import replicas


def test_shared_trace_is_deterministic_and_template_heavy():
    p1, m1 = replicas.make_shared_trace(32, seed=4, n_templates=2,
                                        dup_frac=0.5)
    p2, m2 = replicas.make_shared_trace(32, seed=4, n_templates=2,
                                        dup_frac=0.5)
    assert m1 == m2
    assert all(np.array_equal(a, b) for a, b in zip(p1, p2))
    # duplicates exist (the prefill-skip traffic the tier is built for)
    seen, dups = set(), 0
    for p in p1:
        key = p.tobytes()
        dups += key in seen
        seen.add(key)
    assert dups >= 4
    # and every prompt is template + suffix sized
    assert all(len(p) == 32 for p in p1)


def test_replica_env_pins_one_host_device():
    """Off the TPU a replica keeps the parent's devices; given its slice
    port it is pinned to TPU chip ``idx`` alone."""
    env = replicas.replica_env(3)
    assert "XLA_FLAGS" not in env and "TPU_VISIBLE_CHIPS" not in env
    assert env["DIMA_REPLICA"] == "3"
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    env = replicas.replica_env(3, port=8479)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == "localhost:8479"
    assert "ALLOW_MULTIPLE_LIBTPU_LOAD" not in env


def test_fleet_refuses_more_replicas_than_chips(monkeypatch):
    """On a TPU host every replica needs a chip of its own: the fleet is
    refused before any process starts."""
    monkeypatch.setattr(replicas, "visible_tpu_chips", lambda: 2)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert replicas.tpu_fleet()
    with pytest.raises(ValueError, match="3 replicas need one TPU chip"):
        replicas.run_fleet(n_replicas=3, n_requests=2)


@pytest.mark.slow
def test_two_replica_fleet_matches_dense_oracle():
    """End-to-end: 2 paged replicas drain an open-loop trace; every
    request completes, tokens match the sequential dense oracle, and the
    report carries the latency/SLO/utilization fields."""
    trace = replicas.make_shared_trace(8, seed=2, max_news=(2, 6))
    rec = replicas.run_fleet(n_replicas=2, rate_rps=20.0, max_batch=4,
                             max_len=64, bucket=32, trace=trace,
                             check_tokens=True, slo_ms=60000.0)
    assert rec["token_identity"] == "ok"
    assert rec["requests"] == 8
    assert rec["tokens"] > 0
    assert set(rec["per_replica"]) == {"replica_0", "replica_1"}
    for rep in rec["per_replica"].values():
        assert rep["jit_traces"]["decode"] <= 1
    assert 0.0 <= rec["slo_attainment"] <= 1.0
    assert rec["latency_p99_s"] >= rec["latency_p50_s"]
    assert rec["fleet_tokens_per_s"] > 0


@pytest.mark.slow
def test_fleet_survives_replica_kill():
    """Fault injection: SIGKILL one replica mid-trace.  The run neither
    hangs nor drops requests — the dispatcher detects the dead worker,
    reroutes its claimed + queued work to the survivor, and the tokens
    on every completed request still match the dense oracle."""
    trace = replicas.make_shared_trace(10, seed=2, max_news=(2, 6))
    rec = replicas.run_fleet(n_replicas=2, rate_rps=20.0, max_batch=4,
                             max_len=64, bucket=32, trace=trace,
                             check_tokens=True, slo_ms=60000.0,
                             kill_after_done=3)
    assert rec["replicas_crashed"] == 1
    assert rec["requests_rerouted"] >= 0
    assert rec["requests"] == 10          # nothing dropped
    assert rec["token_identity"] == "ok"
    # the killed replica never reports final stats; the survivor does
    assert len(rec["per_replica"]) == 1
