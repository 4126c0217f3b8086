"""The program's own spans and scopes on the fused banked path.

* The jitted banked step (``kernels/ops.py::_bank_call``) scopes its
  noise stacks ``dima.noise`` and its kernel launch ``dima.kernel``;
  XLA keeps both in the ops' ``op_name``.
* A ``MultiBankBackend`` matvec/matmat is one ``dima.call`` host span
  (stats ``kind``, ``mode``, ``B``, ``M``) holding ``dima.prep``,
  ``dima.launch`` and ``dima.assemble`` in that order.
* Tracing changes no code and no dispatch count.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import dima
from repro.core import api as api_mod
from repro.core import noise as noise_mod
from repro.core import pipeline as pl
from repro.core.params import DimaParams
from repro.kernels import ops

P = DimaParams()
rng = np.random.default_rng(0)
NB = 4
STORED = jnp.asarray(rng.integers(0, 256, (NB * 16, 256)), jnp.uint8)
QS = jnp.asarray(rng.integers(0, 256, (2, 256)), jnp.uint8)
CHIP = noise_mod.sample_chip(jax.random.PRNGKey(3), P)
KEY = jax.random.PRNGKey(9)
TRIM = np.asarray([0.97, -0.4, 12.5], np.float32)
CHILDREN = ["dima.prep", "dima.launch", "dima.assemble"]


@pytest.mark.parametrize("matvec", [True, False], ids=["matvec", "matmat"])
@pytest.mark.parametrize("mode", ["dp", "md"])
def test_banked_body_scopes_noise_and_kernel(mode, matvec):
    text = ops._bank_call.lower(
        STORED, QS[0] if matvec else QS, CHIP, KEY, None, TRIM, 0,
        params=P, interpret=True, mode=mode, matvec=matvec,
        rows_per=STORED.shape[0] // NB).as_text(debug_info=True)
    locs = [ln for ln in text.splitlines() if ln.startswith("#loc")]
    noise = [ln for ln in locs if "/dima.noise/" in ln]
    kernel = [ln for ln in locs if "/dima.kernel/" in ln]
    launch = f"jit(dima_{mode}_bank_batch)"     # the kernel's own jit
    assert any("threefry" in ln for ln in noise)
    assert any(launch in ln for ln in kernel)
    assert not any(launch in ln for ln in noise)
    assert not any("threefry" in ln for ln in kernel)


def _backend():
    return dima.get_backend("multibank", P, CHIP, inner="pallas",
                            n_banks=NB)


def _host_events(trace_dir, prefix="dima."):
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    prof = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for plane in prof.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(prefix)), key=lambda e: e[1])


@pytest.mark.parametrize("kind", ["matvec", "matmat"])
def test_one_call_span_holds_its_three_children(tmp_path, kind):
    be = _backend()
    q = QS[0] if kind == "matvec" else QS
    op = getattr(be, kind)
    jax.block_until_ready(op(STORED, q, mode="dp", key=KEY, trim=TRIM).code)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(
            op(STORED, q, mode="dp", key=KEY, trim=TRIM).code)
    events = _host_events(str(tmp_path))
    assert [n for n, *_ in events] == ["dima.call"] + CHILDREN
    (_, a, b, stats), *children = events
    assert stats == {"kind": kind, "mode": "dp",
                     "B": 1 if kind == "matvec" else QS.shape[0],
                     "M": STORED.shape[0]}
    ends = [a] + [x for _, s, e, _ in children for x in (s, e)] + [b]
    assert ends == sorted(ends)          # nested, in order, not overlapping


@pytest.mark.parametrize("mode", ["dp", "md"])
@pytest.mark.parametrize("kind", ["matvec", "matmat"])
def test_warm_call_is_one_execution(tmp_path, kind, mode):
    """From the start of ``dima.prep`` to the end of ``dima.assemble`` a
    warm fused-Pallas call runs exactly one compiled program: casts,
    pads, unpad and merge run inside the banked step, and the benchmark's
    host constants (a (lo, hi) window, numpy trim and key) go straight
    into it."""
    be = _backend()
    q = QS[0] if kind == "matvec" else QS
    op = getattr(be, kind)
    kw = dict(mode=mode, key=np.asarray(KEY, np.uint32), trim=TRIM,
              v_range=(0.0, 1.0))
    jax.block_until_ready(op(STORED, q, **kw).code)
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(op(STORED, q, **kw).code)
    events = _host_events(str(tmp_path), prefix="")
    spans = {n: (a, b) for n, a, b, _ in events if n in CHILDREN}
    start, end = spans["dima.prep"][0], spans["dima.assemble"][1]
    runs = [n for n, a, _, _ in events
            if n.endswith("Executable::Execute") and start <= a <= end]
    assert len(runs) == 1, runs


@pytest.mark.parametrize("m", [NB * 16, NB * 16 - 5], ids=["full", "ragged"])
@pytest.mark.parametrize("kind", ["matvec", "matmat"])
def test_tracing_changes_no_code_and_no_dispatch(tmp_path, kind, m):
    be = _backend()
    q = QS[0] if kind == "matvec" else QS
    op = getattr(be, kind)
    out = op(STORED[:m], q, mode="md", key=KEY)
    with api_mod.count_dispatches() as plain:
        out = op(STORED[:m], q, mode="md", key=KEY)
    with jax.profiler.trace(str(tmp_path)):
        with api_mod.count_dispatches() as traced:
            traced_out = op(STORED[:m], q, mode="md", key=KEY)
        jax.block_until_ready(traced_out.code)
    assert plain.n == traced.n == (1 if m == NB * 16 else 2)
    np.testing.assert_array_equal(np.asarray(out.code),
                                  np.asarray(traced_out.code))
    # and the staged launch is the public banked wrapper's
    if m == NB * 16:
        f = getattr(ops, f"dima_md_bank_{kind}")
        code, _ = f(STORED.reshape(NB, -1, 256), q, P, CHIP, key=KEY)
        np.testing.assert_array_equal(
            np.asarray(out.code),
            np.asarray(code.reshape(-1) if kind == "matvec"
                       else pl._merge_banked(code)[0]))
