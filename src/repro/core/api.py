"""Unified ``DimaBackend`` compute API — one signature over the digital,
reference, and Pallas paths (re-exported as ``repro.dima``).

The paper's claims all rest on pushing the *same* operation through the
analog chain and the exact digital reference.  This module is the ISA
boundary between the application layer and the substrate: every backend
exposes ``dot`` / ``manhattan`` / ``matvec`` / ``matmat`` with the single
signature ``(stored, query, *, mode, key, v_range) -> DimaOut``, plus a
``decision_cost`` energy/timing model, so applications, serving, and
benchmarks never care which substrate runs the op.

Backends (``get_backend(name | "auto")``):

- ``digital``   — exact 8-b arithmetic (the conventional architecture);
                  ``volts`` is the ideal linear transfer so the parity
                  suite can compare codes against the analog chain.
- ``reference`` — the jnp behavioral model (core/pipeline.py), fully
                  vectorized: a 4096×256 matvec is one jit dispatch.
- ``pallas``    — the TPU kernels (kernels/ops.py); the chip-record →
                  explicit-noise-array expansion happens inside the
                  backend, callers never see the kernel signature.
- ``multibank`` — the paper's multi-bank scenario *executed*: stored rows
                  sharded over ``n_banks`` banks, one matvec/matmat run as
                  ONE dispatch — the bank axis is a real vmap (reference
                  inner) or a leading kernel-grid dimension (pallas
                  inner) — per-bank ADC codes merged digitally; costs
                  amortize the fixed CTRL energy
                  (``decision_cost(multi_bank=True)``).  With a device
                  mesh it fans out via ``shard_map`` over a ``banks``
                  axis (distributed/sharding.py), matvec and matmat both.
                  ``fused=False`` restores the per-bank loop of inner
                  dispatches (the parity oracle).
- ``auto``      — per-call dispatch: Pallas for large banked batches,
                  reference otherwise; the row-count threshold comes from
                  the measured crossover in BENCH_dima_api.json when a
                  benchmark run has produced one.
- ``bitserial`` — bit-scalable precision: the stored 8-b words split into
                  ``n_planes`` bit planes (quant/bitplanes.py), every
                  plane executed as its own analog op with the planes
                  riding a leading vmap/kernel-grid axis inside ONE
                  dispatch, then recombined by a shifted digital
                  accumulate.  ``n_planes=1`` delegates verbatim to the
                  reference path (paper-exact binary behavior);
                  ``decision_cost`` bills per plane
                  (``energy.bitserial_decision``).

Ops on >256-dim vectors go through :func:`chunked_dot` — one ADC
conversion per 256-dim segment, decoded codes summed digitally (exactly
the prototype's dataflow).
"""
from __future__ import annotations

import difflib
import json
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adc as adc_mod
from repro.core import energy as energy_mod
from repro.core import noise as noise_mod
from repro.core import pipeline as pl
from repro.core.params import BankVariation, DimaParams
from repro.core.pipeline import DimaOut

MODES = ("dp", "md")

# ---------------------------------------------------------------------------
# dispatch accounting: every place a backend hands a computation to the
# runtime (a jitted callable, a Pallas launch, a shard_map) goes through
# ``_dispatch`` so the benchmark suite can assert dispatch counts instead
# of inferring them from platform-dependent timings.  Launches traced
# into an enclosing jit are NOT counted (they execute as part of the
# outer computation) — that is what makes "fused multibank matvec == 1
# dispatch" a real claim rather than a bookkeeping artifact.
# ---------------------------------------------------------------------------

_DISPATCH_COUNT = [0]


def _dispatch(thunk):
    """Run ``thunk`` (a zero-arg closure over one compiled-computation
    launch), counting it only when executed for real: a launch traced
    into an enclosing jit (or vmap) returns tracers, not arrays."""
    out = thunk()
    if not any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(out)):
        _DISPATCH_COUNT[0] += 1
    return out


class count_dispatches:
    """``with count_dispatches() as c: ... ; c.n`` — the number of
    compiled-computation launches the backends issued in the block
    (digital's eager ops are not launches and do not count).  Used by
    ``benchmarks/run.py --smoke`` to guard the fused multibank path
    against silently regressing to the per-bank loop."""

    def __enter__(self) -> "count_dispatches":
        self._start = _DISPATCH_COUNT[0]
        self.n = 0
        return self

    def __exit__(self, *exc) -> bool:
        self.n = _DISPATCH_COUNT[0] - self._start
        return False


# Host spans on the profiler's clock: ``dima.call`` around a multibank
# matvec/matmat (stats kind, mode, B queries, M stored rows) and, on the
# single-launch paths, its children ``dima.prep`` (host bookkeeping before
# the launch), ``dima.launch`` (the enqueue of the one execution) and
# ``dima.assemble`` (the output, the ragged bank).  Under
# ``jax.profiler.trace`` they land beside the device ops; with tracing off
# each costs about a microsecond.
_span = jax.profiler.TraceAnnotation


def _check_mode(mode: str) -> None:
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")


def _check_op_dims(n: int, p: DimaParams) -> None:
    """One op = one ADC conversion (two charge-shared access cycles);
    uniform across backends so a >256-dim misuse fails loudly everywhere
    instead of silently saturating the ADC range."""
    if n > p.dims_per_conversion:
        raise ValueError(
            f"one op is one ≤{p.dims_per_conversion}-dim conversion "
            f"(got n={n}); split long vectors with chunked_dot")


def _trim_coef(trim):
    """Normalize a ``trim=`` argument to the (3,) f32 coefficient operand
    the jitted bodies take (None passes through — structure keys the jit
    cache, so no static flag is needed)."""
    return (None if trim is None
            else jnp.asarray(trim, jnp.float32).reshape(3))


def _trim_eager(code, query, coef, p, v_range, mode, per_query=False):
    """Host-side fused-epilogue fallback for paths that have no jitted
    body of their own (digital, the robust per-bank loop): one
    ``pipeline.trim_epilogue`` over the emitted codes.  ``per_query``
    reshapes Σq to (b, 1) so it broadcasts against (b, m) matmat codes."""
    q_sum = jnp.asarray(query).astype(jnp.float32).sum(-1)
    if per_query:
        q_sum = q_sum[:, None]
    return pl.trim_epilogue(code, q_sum, coef, p, v_range, mode)


class DimaBackend:
    """Base class / protocol for one compute substrate.

    A backend instance owns the circuit parameters ``p`` and one silicon
    instance ``chip`` (fixed-pattern mismatch record, or None = ideal);
    per-call state is the data, the dynamic-noise ``key``, and the
    programmed ADC ``v_range``.  ``DimaOut.n_cycles``/``n_conversions``
    follow core/pipeline.py conventions: per-op counts for ``dot`` /
    ``manhattan``, totals for ``matvec`` / ``matmat``.
    """

    name = "abstract"
    # True only for substrates that actually execute bank-sharded — drives
    # the serving layer's per-token energy switching (amortized CTRL cost)
    executes_multibank = False

    def __init__(self, p: DimaParams = None, chip=None):
        self.p = p if p is not None else DimaParams()
        self.chip = chip

    def ideal(self) -> "DimaBackend":
        """The same substrate with an ideal chip (no fixed-pattern
        mismatch) — what range calibration runs on."""
        return type(self)(self.p, None)

    # -- the one signature --------------------------------------------------
    #
    # ``trim=(c0, c1, c2)`` on any op switches on the fused calibration
    # epilogue: the op additionally returns ``DimaOut.trimmed``, the
    # affine-trimmed score ``c0·d̂ + c1·Σq + c2`` (pipeline.trim_epilogue)
    # computed inside the op's own launch/jit wherever the substrate has
    # one.  Codes/volts (and dispatch counts) are unchanged by ``trim``.

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        """One ≤256-dim op per trailing dim; leading dims broadcast."""
        raise NotImplementedError

    def manhattan(self, stored, query, *, mode="md", key=None,
                  v_range=None, trim=None) -> DimaOut:
        return self.dot(stored, query, mode=mode, key=key, v_range=v_range,
                        trim=trim)

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        """All stored rows (m, n≤256) against one query (n,)."""
        raise NotImplementedError

    def matmat(self, stored, queries, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        """stored (m, n) × queries (b, n) -> codes (b, m); per-query keys
        are ``jax.random.split(key, b)`` on every backend."""
        queries = jnp.asarray(queries)
        b = queries.shape[0]
        keys = (jax.random.split(key, b) if key is not None else [None] * b)
        outs = [self.matvec(stored, queries[j], mode=mode, key=keys[j],
                            v_range=v_range, trim=trim) for j in range(b)]
        trimmed = (None if trim is None
                   else jnp.stack([o.trimmed for o in outs]))
        return DimaOut(jnp.stack([o.code for o in outs]),
                       jnp.stack([o.volts for o in outs]),
                       sum(o.n_cycles for o in outs),
                       sum(o.n_conversions for o in outs), trimmed)

    # -- decode / cost ------------------------------------------------------

    def decode(self, code, *, mode="dp", v_range=None):
        """ADC code -> operation units (dot value or Manhattan distance)."""
        _check_mode(mode)
        f = pl.code_to_dot if mode == "dp" else pl.code_to_md
        return f(code, self.p, v_range)

    def decision_cost(self, n_dims: int, *, mode="dp", n_ops=1,
                      multi_bank=False, **kw) -> energy_mod.Cost:
        """Modeled energy/timing of one decision on this substrate."""
        return energy_mod.dima_decision(self.p, n_dims, mode=mode,
                                        n_ops=n_ops, multi_bank=multi_bank,
                                        **kw)


# ---------------------------------------------------------------------------
# registry / factory
# ---------------------------------------------------------------------------

BACKENDS: dict = {}


def register_backend(name: str):
    """Class decorator: make a backend constructible via get_backend —
    the plug-in point for future substrates (multi-bank sharded, ...)."""
    def deco(cls):
        cls.name = name
        BACKENDS[name] = cls
        return cls
    return deco


def get_backend(name: str = "auto", p: DimaParams = None, chip=None,
                **kwargs) -> DimaBackend:
    """Factory: ``get_backend("digital" | "reference" | "pallas" |
    "multibank" | "auto")``.

    Accepts an already-constructed backend — anything that isn't a name
    string, e.g. a ``DimaBackend`` or a duck-typed wrapper around one —
    and returns it unchanged, so call sites can take
    ``backend: str | DimaBackend`` parameters.  Raises ``KeyError``
    listing the registered names (and the closest match) on a typo.
    """
    if not isinstance(name, str):
        return name
    if name not in BACKENDS:
        close = difflib.get_close_matches(str(name), BACKENDS, n=1)
        hint = f" — did you mean {close[0]!r}?" if close else ""
        raise KeyError(f"unknown backend {name!r}; registered backends: "
                       f"{sorted(BACKENDS)}{hint}")
    return BACKENDS[name](p, chip, **kwargs)


# ---------------------------------------------------------------------------
# digital: exact 8-b arithmetic (the conventional architecture)
# ---------------------------------------------------------------------------

@register_backend("digital")
class DigitalBackend(DimaBackend):
    """Bit-exact integer compute.  ``volts`` is the *ideal* linear analog
    transfer of the exact result (the value a zero-systematic-error chain
    would develop), so codes/volts are directly comparable to the analog
    backends; ``key`` is accepted and ignored (no noise to sample)."""

    def _gain(self, mode):
        return pl.dp_gain(self.p) if mode == "dp" else pl.md_gain(self.p)

    def _default_range(self, mode):
        full = 255.0 * 255.0 if mode == "dp" else 255.0
        return (0.0, full * self._gain(mode))

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        _check_mode(mode)
        exact_f = pl.digital_dot if mode == "dp" else pl.digital_manhattan
        exact = exact_f(stored, query)
        n = max(jnp.asarray(stored).shape[-1], jnp.asarray(query).shape[-1])
        _check_op_dims(n, self.p)
        v = exact.astype(jnp.float32) / self.p.dims_per_conversion \
            * self._gain(mode)
        if v_range is None:
            v_range = self._default_range(mode)
        code = adc_mod.adc(v, v_range[0], v_range[1], self.p)
        trimmed = (None if trim is None
                   else _trim_eager(code, query, trim, self.p, v_range, mode))
        return DimaOut(code, v, pl._cycles_per_op(n, self.p), 1, trimmed)

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        m = stored.shape[0]
        out = self.dot(stored, query, mode=mode, v_range=v_range, trim=trim)
        return DimaOut(out.code, out.volts, m * out.n_cycles, m, out.trimmed)

    def decision_cost(self, n_dims: int, *, mode="dp", n_ops=1,
                      multi_bank=False, **kw) -> energy_mod.Cost:
        # the conventional fetch-then-compute architecture (no banks)
        return energy_mod.conventional_decision(self.p, n_dims, mode=mode,
                                                n_ops=n_ops)


# ---------------------------------------------------------------------------
# reference: the jnp behavioral model, vectorized
# ---------------------------------------------------------------------------

@register_backend("reference")
class ReferenceBackend(DimaBackend):
    """core/pipeline.py behind the unified signature.  Every entry point
    is jit-compiled once per (op, mode) — the jit cache keys on argument
    structure, so chip/key/v_range may each be present or None."""

    def __init__(self, p: DimaParams = None, chip=None):
        super().__init__(p, chip)
        self._jit = {}

    def _fn(self, kind, mode):
        """Per-(op, mode) jitted body; a trailing ``coef`` operand (None
        or the (3,) trim coefficients — argument *structure* keys the jit
        cache) appends the fused calibration epilogue inside the same
        jit, so ``trim=`` costs zero extra dispatches."""
        _check_mode(mode)
        k = (kind, mode)
        if k not in self._jit:
            p = self.p

            def run(s, q, chip, key, vr, coef):
                if kind == "op":
                    f = pl.dima_dot if mode == "dp" else pl.dima_manhattan
                    code, volts = f(s, q, p, chip, key, vr)[:2]
                    qs = jnp.asarray(q).astype(jnp.float32).sum(-1)
                elif kind == "matmat":
                    code, volts = pl.dima_matmat(s, q, p, chip, key, mode,
                                                 vr)
                    qs = jnp.asarray(q).astype(jnp.float32).sum(-1)[:, None]
                else:
                    code, volts = pl.dima_matvec(s, q, p, chip, key, mode,
                                                 vr)[:2]
                    qs = jnp.asarray(q).astype(jnp.float32).sum(-1)
                if coef is None:
                    return code, volts
                return code, volts, pl.trim_epilogue(code, qs, coef, p, vr,
                                                     mode)

            self._jit[k] = jax.jit(run)
        return self._jit[k]

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        query = jnp.asarray(query)
        n = max(stored.shape[-1], query.shape[-1])
        _check_op_dims(n, self.p)
        out = _dispatch(lambda: self._fn("op", mode)(
            stored, query, self.chip, key, v_range, _trim_coef(trim)))
        return DimaOut(out[0], out[1], pl._cycles_per_op(n, self.p), 1,
                       out[2] if len(out) == 3 else None)

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        m = stored.shape[0]
        _check_op_dims(stored.shape[-1], self.p)
        out = _dispatch(lambda: self._fn("matvec", mode)(
            stored, jnp.asarray(query), self.chip, key, v_range,
            _trim_coef(trim)))
        return DimaOut(out[0], out[1],
                       m * pl._cycles_per_op(stored.shape[-1], self.p), m,
                       out[2] if len(out) == 3 else None)

    def matmat(self, stored, queries, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        queries = jnp.asarray(queries)
        b, m = queries.shape[0], stored.shape[0]
        _check_op_dims(stored.shape[-1], self.p)
        n_cycles = b * m * pl._cycles_per_op(stored.shape[-1], self.p)
        out = _dispatch(lambda: self._fn("matmat", mode)(
            stored, queries, self.chip, key, v_range, _trim_coef(trim)))
        return DimaOut(out[0], out[1], n_cycles, b * m,
                       out[2] if len(out) == 3 else None)


# ---------------------------------------------------------------------------
# pallas: the TPU kernels (interpret mode on CPU)
# ---------------------------------------------------------------------------

@register_backend("pallas")
class PallasBackend(DimaBackend):
    """kernels/ops.py behind the unified signature.  The banked kernels
    take one query against (M, 256) stored rows; this backend pads the
    trailing dim to one conversion and expands the chip record / rng key
    into the kernels' explicit noise operands (ops.py), so the explicit-
    noise signature never leaks to callers.

    Noise caveat: per-read dynamic noise is drawn with the kernels' own
    key-splitting layout, so *noisy* results are statistically — not
    bitwise — equivalent to the reference backend; with ``key=None`` all
    backends agree exactly (the parity suite asserts it).
    """

    # modes the banked kernels implement: anything else must fail loudly
    # (never silently fall back to another substrate — AutoBackend is the
    # only place that is allowed to reroute)
    KERNEL_MODES = ("dp", "md")

    def __init__(self, p: DimaParams = None, chip=None, interpret=None):
        super().__init__(p, chip)
        self.interpret = interpret

    def ideal(self) -> "PallasBackend":
        return PallasBackend(self.p, None, self.interpret)

    def _require_kernel_mode(self, mode):
        _check_mode(mode)
        if mode not in self.KERNEL_MODES:
            raise ValueError(
                f"the pallas banked kernels implement modes "
                f"{self.KERNEL_MODES}, not {mode!r} — use "
                f"get_backend('reference') (or 'auto', which routes "
                f"unsupported modes there) for this op")

    def _banked(self, stored, query, mode, key, v_range, trim=None):
        from repro.kernels import ops as kops
        self._require_kernel_mode(mode)
        stored = jnp.asarray(stored)
        query = jnp.asarray(query)
        _check_op_dims(stored.shape[-1], self.p)
        d = pl._pad_to_conversion(stored.astype(jnp.int32), self.p)
        q = pl._pad_to_conversion(query.astype(jnp.int32), self.p)
        f = kops.dima_dp_banked if mode == "dp" else kops.dima_md_banked
        return _dispatch(lambda: f(
            d.astype(jnp.uint8), q.astype(jnp.uint8), self.p, self.chip,
            key, v_range, interpret=self.interpret, trim=trim))

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        """Decomposes onto the banked kernels.  Besides (n,)/(m, n) × (n,),
        the two broadcast layouts the applications/calibration use are
        routed through matmat: one stored row × a query batch
        ((1, n) × (B, n) -> (B,)) and a stored bank × a query batch
        ((1, m, n) × (b, 1, n) -> (b, m))."""
        self._require_kernel_mode(mode)
        stored = jnp.asarray(stored)
        query = jnp.asarray(query)
        per_op = pl._cycles_per_op(stored.shape[-1], self.p)

        def _sl(t, idx):
            return None if t is None else t[idx]

        if stored.ndim == 1:
            out = self.matvec(stored[None, :], query, mode=mode, key=key,
                              v_range=v_range, trim=trim)
            return DimaOut(out.code[0], out.volts[0], per_op, 1,
                           _sl(out.trimmed, 0))
        if stored.ndim == 2 and query.ndim == 1:
            out = self.matvec(stored, query, mode=mode, key=key,
                              v_range=v_range, trim=trim)
            return DimaOut(out.code, out.volts, per_op, 1, out.trimmed)
        if stored.ndim == 2 and stored.shape[0] == 1 and query.ndim == 2:
            out = self.matmat(stored, query, mode=mode, key=key,
                              v_range=v_range, trim=trim)
            return DimaOut(out.code[:, 0], out.volts[:, 0], per_op, 1,
                           _sl(out.trimmed, (slice(None), 0)))
        if (stored.ndim == 3 and stored.shape[0] == 1 and query.ndim == 3
                and query.shape[1] == 1):
            out = self.matmat(stored[0], query[:, 0, :], mode=mode, key=key,
                              v_range=v_range, trim=trim)
            return DimaOut(out.code, out.volts, per_op, 1, out.trimmed)
        raise ValueError(
            f"pallas backend supports stored (n,)/(m, n) × query (n,), "
            f"(1, n) × (B, n), or (1, m, n) × (b, 1, n); got "
            f"{stored.shape} × {query.shape} — use the reference backend "
            "for general broadcasts")

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        if stored.ndim != 2:
            raise ValueError(f"matvec wants stored (m, n); got "
                             f"{stored.shape}")
        m = stored.shape[0]
        out = self._banked(stored, query, mode, key, v_range, trim)
        return DimaOut(out[0], out[1],
                       m * pl._cycles_per_op(stored.shape[-1], self.p), m,
                       out[2] if len(out) == 3 else None)

    def matmat(self, stored, queries, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        """ONE kernel launch for the whole (b, m) code matrix: the query
        batch rides the first grid axis (kernels/ops.py matmat wrappers)
        instead of the base class's per-query Python loop.  Per-query keys
        are ``jax.random.split(key, b)`` like every other backend (the
        per-read layout within a query follows the kernels' convention,
        so noisy codes are statistically — not bitwise — equivalent to
        reference; with ``key=None`` all backends agree exactly)."""
        from repro.kernels import ops as kops
        self._require_kernel_mode(mode)
        stored = jnp.asarray(stored)
        queries = jnp.asarray(queries)
        if stored.ndim != 2 or queries.ndim != 2:
            raise ValueError(f"matmat wants stored (m, n) × queries "
                             f"(b, n); got {stored.shape} × {queries.shape}")
        _check_op_dims(stored.shape[-1], self.p)
        b, m = queries.shape[0], stored.shape[0]
        d = pl._pad_to_conversion(stored.astype(jnp.int32), self.p)
        q = pl._pad_to_conversion(queries.astype(jnp.int32), self.p)
        f = kops.dima_dp_matmat if mode == "dp" else kops.dima_md_matmat
        out = _dispatch(lambda: f(
            d.astype(jnp.uint8), q.astype(jnp.uint8), self.p, self.chip,
            key, v_range, interpret=self.interpret, trim=trim))
        return DimaOut(out[0], out[1],
                       b * m * pl._cycles_per_op(stored.shape[-1], self.p),
                       b * m, out[2] if len(out) == 3 else None)


# ---------------------------------------------------------------------------
# multibank: the paper's multi-bank scenario, executed
# ---------------------------------------------------------------------------

def _bank_matvec(d_b, q, p, chip, bank_key, mode, v_range):
    """One bank's matvec — the single per-bank core every multibank path
    (host fused, host loop via ReferenceBackend, mesh shard) runs, so
    they cannot drift apart."""
    return pl.dima_matvec(d_b, q, p, chip, bank_key, mode, v_range)[:2]


def _bank_matmat(d_b, qs, p, chip, bank_key, mode, v_range):
    """One bank's matmat (per-query keys = ``split(bank_key, b)``, the
    convention ``pl.dima_matmat`` defines once)."""
    return pl.dima_matmat(d_b, qs, p, chip, bank_key, mode, v_range)


@register_backend("multibank")
class MultiBankBackend(DimaBackend):
    """Bank-sharded execution: ``stored`` rows are split into ``n_banks``
    banks (contiguous row blocks, last bank ragged when the row count
    does not divide), one ``matvec``/``matmat`` fans out over the banks,
    and the per-bank ADC codes are merged digitally — a concatenation,
    because each row's decision is exact-per-bank; the merge cost sits in
    the CTRL budget that ``decision_cost`` amortizes over the banks
    (``energy.bank_fixed_split``).

    Execution (``fused=True``, the default) is a SINGLE dispatch: the
    full banks are reshaped to ``(n_banks, rows_per, n)`` and the inner
    pipeline is vmapped over the bank axis inside one per-(op, mode) jit
    (``jax.jit`` retraces per bank count/shape, so the cache is
    effectively per (op, mode, n_banks)); a ragged last bank is a second
    branch *inside the same jitted computation* — the banks execute
    concurrently exactly as the paper's 32-bank scenario assumes, instead
    of the ``fused=False`` per-bank Python loop of inner dispatches
    (kept as the bitwise test oracle and benchmark baseline).  With a
    Pallas inner, the full banks are ONE kernel launch over a
    ``(n_banks, B, rows/128)`` grid (kernels/ops.py ``*_bank_*``); the
    ragged remainder — whose noise-array shape differs, and JAX's
    counter-based PRNG is not prefix-stable — is the inner backend's own
    single-bank launch, so ragged Pallas splits cost exactly 2 dispatches.
    Fusion exists for the ``reference`` and ``pallas`` inners only: any
    other single-bank inner (e.g. ``digital``) executes as the per-bank
    loop regardless of ``fused`` — one inner dispatch per occupied bank,
    which ``count_dispatches`` reports faithfully.

    Keys: bank ``b`` draws an independent stream via
    ``jax.random.fold_in(key, b)``; within a bank the inner backend's own
    per-row/per-query layout applies.  So a multibank matvec is bit-for-
    bit the digital merge of per-bank inner runs with those keys — the
    parity the test suite asserts for the fused and loop paths alike.

    Mesh fan-out: pass ``mesh`` (a ``jax.sharding.Mesh`` with a ``banks``
    axis, see ``distributed.sharding.bank_mesh``, or a ``ShardCtx``) and
    matvec/matmat run as one ``shard_map`` over the bank axis.  With the
    default ``reference`` inner each device vmaps the same per-bank core
    over its local banks; with a ``pallas`` inner each device runs ONE
    banked kernel launch (kernels/ops.py ``*_bank_*``) over its local
    banks — the kernel-only device path, so an accelerator shard never
    falls back to the jnp pipeline.  Both use ``bank_offset = axis_index
    * local_banks`` to resume the ``fold_in(key, b)`` streams where the
    previous shard stopped, so ADC codes are bitwise equal to the host
    fused path bank-for-bank (the oracle; volts and the fused trimmed
    output agree to the float-assembly tolerance — interpret-mode Pallas
    compiles through XLA, which may reassociate by ~1 ulp across
    program contexts).  The merge is the
    sharded-to-replicated gather.  The mesh path requires the row count
    to divide ``n_banks`` (no ragged last bank across devices).

    Fleet robustness (all off by default — a default-constructed backend
    is bitwise-identical to the seed):

    * ``variation`` (a ``params.BankVariation``) + ``variation_key``
      give every *physical* bank its own silicon (chip-to-chip sigma
      scaling, ``noise.sample_bank_chips``) and/or a temporal drift walk
      advanced by :meth:`advance_epoch` (``noise.step_drift`` folded
      into the bank chip records).
    * ``faults`` (a ``distributed.fault_tolerance.FaultSchedule``)
      injects dead / stuck / drifted banks over epoch windows; the
      backend's ``epoch`` (ticked by ``advance_epoch``) is the schedule
      clock.
    * ``redundancy=R`` stores each logical bank's rows on ``R``
      physical banks (replica-major: physical bank ``r·n_banks + b`` is
      replica ``r`` of logical bank ``b``) and the digital merge takes
      the per-element median code over replicas — an ECC-style vote
      that masks a dead or stuck replica outright.  Energy honesty:
      cycle/conversion counts scale by ``R``.
    * :meth:`recalibrate_banks` measures each physical bank's affine
      voltage transfer against the clean chip and reprograms the bank's
      ADC window along it (the drift-aware per-bank ``v_range``
      refresh) — the digital countermeasure that pulls a drifted bank
      back to the clean operating point; a dead/stuck bank yields
      degenerate probes and keeps the identity transfer (voting handles
      it instead).

    When any of these is active, matvec/matmat run a per-physical-bank
    loop of reference-pipeline dispatches (the robust path needs
    per-bank chip records, which the fused/mesh/pallas paths do not
    thread); with everything at defaults the fused single-dispatch
    paths are untouched.  At ``redundancy=1`` with no variation, no
    faults and no trim, the robust path is bit-for-bit the existing
    ``fused=False`` loop (same ``fold_in(key, b)`` streams) — the
    parity the test suite asserts.
    """

    executes_multibank = True

    def __init__(self, p: DimaParams = None, chip=None, inner="reference",
                 n_banks: int = None, mesh=None, fused: bool = True,
                 variation: BankVariation = None, variation_key=None,
                 faults=None, redundancy: int = 1):
        super().__init__(p, chip)
        self.n_banks = (self.p.n_banks_multibank if n_banks is None
                        else int(n_banks))
        if self.n_banks < 1:
            raise ValueError(f"n_banks must be >= 1; got {self.n_banks}")
        self.inner = (inner if isinstance(inner, DimaBackend)
                      else get_backend(inner, self.p, chip))
        if self.inner.executes_multibank:
            raise ValueError("inner backend must be a single-bank substrate")
        self.mesh = getattr(mesh, "mesh", mesh)   # ShardCtx | Mesh | None
        if self.mesh is not None and not isinstance(
                self.inner, (ReferenceBackend, PallasBackend)):
            # fail loudly instead of silently diverging from the host path
            raise ValueError(
                f"mesh fan-out runs the reference pipeline or the banked "
                f"Pallas kernels per shard; inner={self.inner.name!r} is "
                "only available on the host path (mesh=None)")
        self.fused = bool(fused)
        self._jit = {}
        # -- fleet robustness state (inert at defaults) ---------------------
        self.variation = variation
        self.variation_key = variation_key
        self.faults = faults
        self.redundancy = int(redundancy)
        if self.redundancy < 1:
            raise ValueError(f"redundancy must be >= 1; got "
                             f"{self.redundancy}")
        self.epoch = 0
        self._drift = None        # noise.DriftState over physical banks
        self._bank_chips = None   # stacked per-physical-bank chip records
        self._trim = None         # (a, c) per-physical-bank affine code trim
        if variation is not None and variation.varies and variation_key is \
                None:
            raise ValueError("BankVariation with sigma_scale != 0 needs a "
                             "variation_key to draw the bank population")
        if self.robust:
            if self.mesh is not None:
                raise ValueError("variation/faults/redundancy run on the "
                                 "host per-bank path; mesh fan-out does not "
                                 "thread per-bank chip records — use "
                                 "mesh=None")
            if not isinstance(self.inner, ReferenceBackend):
                raise ValueError(
                    f"the robust path runs the reference pipeline per "
                    f"physical bank; inner={self.inner.name!r} is only "
                    "available with robustness off")

    @property
    def robust(self) -> bool:
        """True when any fleet-robustness feature routes matvec/matmat
        to the per-physical-bank path."""
        return (self.redundancy > 1 or bool(self.faults)
                or (self.variation is not None and self.variation.enabled)
                or self._trim is not None)

    @property
    def n_physical(self) -> int:
        return self.n_banks * self.redundancy

    def ideal(self) -> "MultiBankBackend":
        """The clean substrate range calibration runs on: no mismatch,
        no variation, no faults, no redundancy."""
        return MultiBankBackend(self.p, None, inner=self.inner.ideal(),
                                n_banks=self.n_banks, mesh=self.mesh,
                                fused=self.fused)

    def bank_slices(self, m: int):
        """Contiguous (start, stop) row blocks, one per occupied bank;
        the last bank is ragged when n_banks does not divide m, and
        trailing banks are empty (skipped) when m < n_banks."""
        rows_per = -(-m // self.n_banks)             # ceil
        return [(a, min(a + rows_per, m)) for a in range(0, m, rows_per)]

    def _bank_split(self, m: int):
        """(rows_per, n_full, ragged): ``n_full`` banks of exactly
        ``rows_per`` rows plus one trailing bank of ``ragged`` rows —
        the same partition ``bank_slices`` yields, in the reshapeable
        form the fused paths stack on a bank axis."""
        rows_per = -(-m // self.n_banks)             # ceil
        n_full = m // rows_per
        return rows_per, n_full, m - n_full * rows_per

    def _bank_key(self, key, b):
        return None if key is None else jax.random.fold_in(key, b)

    @staticmethod
    def _merge(outs, axis=0) -> DimaOut:
        """The digital merge: per-bank code/volt blocks concatenated in
        row order (each decision is already exact-per-bank), cycle and
        conversion counts summed — total work is bank-count invariant.
        ``trimmed`` merges like codes when every bank carries one."""
        trimmed = None
        if all(o.trimmed is not None for o in outs):
            trimmed = jnp.concatenate([o.trimmed for o in outs], axis)
        return DimaOut(jnp.concatenate([o.code for o in outs], axis),
                       jnp.concatenate([o.volts for o in outs], axis),
                       sum(o.n_cycles for o in outs),
                       sum(o.n_conversions for o in outs), trimmed)

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        """A single op occupies a single bank: straight delegation (the
        cost model still amortizes, which is exactly the paper's † rows —
        31 other banks work on other decisions concurrently)."""
        return self.inner.dot(stored, query, mode=mode, key=key,
                              v_range=v_range, trim=trim)

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        if stored.ndim != 2:
            raise ValueError(f"matvec wants stored (m, n); got "
                             f"{stored.shape}")
        _check_op_dims(stored.shape[-1], self.p)
        with _span("dima.call", kind="matvec", mode=mode, B=1,
                   M=stored.shape[0]):
            if self.robust:
                return self._robust_run("matvec", stored,
                                        jnp.asarray(query), mode, key,
                                        v_range, trim)
            if self.mesh is not None:
                return self._matvec_mesh(stored, jnp.asarray(query), mode,
                                         key, v_range, trim)
            if self.fused and isinstance(self.inner, ReferenceBackend):
                return self._fused_host("matvec", stored,
                                        jnp.asarray(query), mode, key,
                                        v_range, trim)
            if self.fused and isinstance(self.inner, PallasBackend):
                return self._fused_pallas("matvec", stored,
                                          jnp.asarray(query), mode, key,
                                          v_range, trim)
            return self._merge(
                [self.inner.matvec(stored[a:z], query, mode=mode,
                                   key=self._bank_key(key, b),
                                   v_range=v_range, trim=trim)
                 for b, (a, z) in enumerate(
                     self.bank_slices(stored.shape[0]))],
                axis=0)

    def matmat(self, stored, queries, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        stored = jnp.asarray(stored)
        queries = jnp.asarray(queries)
        if stored.ndim != 2 or queries.ndim != 2:
            raise ValueError(f"matmat wants stored (m, n) × queries "
                             f"(b, n); got {stored.shape} × {queries.shape}")
        _check_op_dims(stored.shape[-1], self.p)
        with _span("dima.call", kind="matmat", mode=mode, B=queries.shape[0],
                   M=stored.shape[0]):
            if self.robust:
                return self._robust_run("matmat", stored, queries, mode, key,
                                        v_range, trim)
            if self.mesh is not None:
                return self._matmat_mesh(stored, queries, mode, key,
                                         v_range, trim)
            if self.fused and isinstance(self.inner, ReferenceBackend):
                return self._fused_host("matmat", stored, queries, mode, key,
                                        v_range, trim)
            if self.fused and isinstance(self.inner, PallasBackend):
                return self._fused_pallas("matmat", stored, queries, mode,
                                          key, v_range, trim)
            return self._merge(
                [self.inner.matmat(stored[a:z], queries, mode=mode,
                                   key=self._bank_key(key, b),
                                   v_range=v_range, trim=trim)
                 for b, (a, z) in enumerate(
                     self.bank_slices(stored.shape[0]))],
                axis=1)

    # -- robust path: per-physical-bank loop with variation/drift/faults ----

    def advance_epoch(self, key=None) -> int:
        """One epoch tick (the owner defines the cadence — wall clock,
        tokens, requests): advances the fault-schedule clock and, when
        the variation model drifts, steps every physical bank's
        gain/offset walk.  Returns the new epoch."""
        self.epoch += 1
        if self.variation is not None and self.variation.drifts:
            if self._drift is None:
                self._drift = noise_mod.init_drift(self.n_physical)
            self._drift = noise_mod.step_drift(self._drift, key,
                                               self.variation)
        return self.epoch

    @property
    def drift_state(self):
        return self._drift

    def _physical_chips(self):
        """Stacked per-physical-bank chip records with the current drift
        walk folded in.  With chip-to-chip variation each bank is its
        own severity-scaled silicon; otherwise every bank carries the
        backend's base chip (or the ideal record) so drift still has a
        concrete record to walk."""
        if self._bank_chips is None:
            if self.variation is not None and self.variation.varies:
                self._bank_chips = noise_mod.sample_bank_chips(
                    self.variation_key, self.p, self.n_physical,
                    self.variation)
            else:
                base = (self.chip if self.chip is not None
                        else noise_mod.ideal_chip(self.p))
                self._bank_chips = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(
                        x, (self.n_physical,) + x.shape), base)
        chips = self._bank_chips
        if self._drift is not None:
            chips = noise_mod.apply_drift(chips, self._drift)
        return chips

    def _active_faults(self) -> dict:
        """{physical bank -> BankFault} in effect this epoch (later
        schedule entries win on the same bank)."""
        if not self.faults:
            return {}
        return {f.bank: f for f in self.faults.active(self.epoch)}

    def _robust_fn(self, kind, mode):
        """The per-(op, mode) jitted per-bank core with the chip record
        as an *operand* — every physical bank reuses the one compiled
        computation (per row-count shape), just with its own record."""
        _check_mode(mode)
        k = ("robust", kind, mode)
        if k not in self._jit:
            p = self.p
            core = _bank_matvec if kind == "matvec" else _bank_matmat
            self._jit[k] = jax.jit(
                lambda d_b, q, chip, key, vr: core(d_b, q, p, chip, key,
                                                   mode, vr))
        return self._jit[k]

    def _fault_codes(self, f, code, volts):
        """Post-conversion fault transfer: a dead bank's ADC reads the
        collapsed rail, a stuck bank pins at one code (the analog node
        still develops, so volts stay)."""
        if f.kind == "dead":
            return jnp.zeros_like(code), jnp.zeros_like(volts)
        if f.kind == "stuck":
            return jnp.full_like(code, f.stuck_code), volts
        return code, volts                     # drifted acts on the chip

    def _replica_codes(self, fn, rows, q, pb, chips, faults, key, v_range):
        """One physical bank's codes: its own chip record (+ hard-drift
        fault gain), its own fold_in stream, its recalibrated ADC range,
        post-conversion fault transfer."""
        chip_b = jax.tree_util.tree_map(lambda x: x[pb], chips)
        f = faults.get(pb)
        if f is not None and f.kind == "drifted":
            chip_b = dict(chip_b, col_gain=chip_b["col_gain"] * f.gain)
        vr_b = v_range
        if self._trim is not None and v_range is not None:
            # drift-aware per-bank range: the ADC window rides the bank's
            # measured affine transfer v -> g·v + o, so the code for a
            # drifted signal equals the clean code for the clean signal
            g, o = self._trim
            vr_b = (g[pb] * v_range[0] + o[pb], g[pb] * v_range[1] + o[pb])
        code, volts = _dispatch(lambda: fn(rows, q, chip_b,
                                           self._bank_key(key, pb), vr_b))
        if f is not None:
            code, volts = self._fault_codes(f, code, volts)
        return code, volts

    def _robust_run(self, kind, stored, q, mode, key, v_range,
                    trim=None) -> DimaOut:
        """matvec/matmat over the physical fleet: every logical bank's
        rows run on its R replicas, the digital merge is the per-element
        median code over replicas (R=1: identity — bit-for-bit the
        ``fused=False`` loop), logical banks concatenate in row order
        as always.  ``trim`` runs the epilogue once over the merged
        codes (the loop has no fused body to ride)."""
        m = stored.shape[0]
        R, nb = self.redundancy, self.n_banks
        chips = self._physical_chips()
        faults = self._active_faults()
        fn = self._robust_fn(kind, mode)
        codes, volts = [], []
        for b, (s0, s1) in enumerate(self.bank_slices(m)):
            reps = [self._replica_codes(fn, stored[s0:s1], q, r * nb + b,
                                        chips, faults, key, v_range)
                    for r in range(R)]
            if R == 1:
                c_b, v_b = reps[0]
            else:
                # median over the replica axis: ints stay exact, and with
                # one dead/stuck replica the two healthy codes outvote it
                c_b = jnp.sort(jnp.stack([c for c, _ in reps]), 0)[R // 2]
                v_b = jnp.sort(jnp.stack([v for _, v in reps]), 0)[R // 2]
            codes.append(c_b)
            volts.append(v_b)
        axis = 0 if kind == "matvec" else 1
        n_ops = m if kind == "matvec" else q.shape[0] * m
        code = jnp.concatenate(codes, axis)
        trimmed = (None if trim is None
                   else _trim_eager(code, q, trim, self.p, v_range, mode,
                                    per_query=(kind == "matmat")))
        return DimaOut(code, jnp.concatenate(volts, axis),
                       R * n_ops * pl._cycles_per_op(stored.shape[-1],
                                                     self.p),
                       R * n_ops, trimmed)

    def recalibrate_banks(self, stored, cal_queries, *, mode="dp",
                          v_range=None):
        """The digital countermeasure: probe every physical bank with
        zero-noise calibration queries, fit its affine voltage transfer
        against the clean chip (``v_drifted ≈ g·v_clean + o``, lstsq),
        and reprogram the bank's ADC window along that transfer — the
        drift-aware ``v_range`` refresh.  Because the single-slope code
        is range-relative, a bank whose signal shrank to ``g·v + o``
        digitized over ``(g·v_lo + o, g·v_hi + o)`` emits the *clean*
        code again, even when drift has railed the signal out of the
        nominal window entirely (a code-domain trim cannot recover
        that — the information is gone at the ADC).  A dead or stuck
        bank yields degenerate probes and keeps the identity transfer;
        redundancy voting is the countermeasure there.  Returns the
        per-physical-bank (gain, offset) arrays."""
        stored = jnp.asarray(stored)
        q = jnp.asarray(cal_queries)
        R, nb = self.redundancy, self.n_banks
        chips = self._physical_chips()
        faults = self._active_faults()
        fn = self._robust_fn("matmat", mode)
        trim_prev, self._trim = self._trim, None   # probe raw transfers
        g_arr = np.ones(self.n_physical)
        o_arr = np.zeros(self.n_physical)
        try:
            for b, (s0, s1) in enumerate(self.bank_slices(stored.shape[0])):
                _, v_clean = _dispatch(lambda: fn(stored[s0:s1], q, None,
                                                  None, v_range))
                x = np.asarray(v_clean, dtype=np.float64).ravel()
                for r in range(R):
                    pb = r * nb + b
                    _, v_bank = self._replica_codes(fn, stored[s0:s1], q, pb,
                                                    chips, faults, None,
                                                    v_range)
                    y = np.asarray(v_bank, dtype=np.float64).ravel()
                    if y.std() > 1e-9 and x.std() > 1e-9:
                        coef, *_ = np.linalg.lstsq(
                            np.stack([x, np.ones_like(x)], 1), y, rcond=None)
                        g_arr[pb], o_arr[pb] = coef
        except Exception:
            self._trim = trim_prev
            raise
        self._trim = (jnp.asarray(g_arr, jnp.float32),
                      jnp.asarray(o_arr, jnp.float32))
        return self._trim

    def clear_trim(self) -> None:
        self._trim = None

    # -- fused host path (reference inner): one jit dispatch ----------------

    def _fused_fn(self, kind, mode):
        """The per-(op, mode) jitted fused computation: vmap the per-bank
        core over the stacked full banks, run the ragged remainder (if
        any) as a second branch of the SAME computation, concatenate.
        ``jax.jit`` retraces per argument structure, so bank count,
        raggedness, chip/key presence all key the cache automatically."""
        _check_mode(mode)
        k = (kind, mode)
        if k not in self._jit:
            p, core = self.p, (_bank_matvec if kind == "matvec"
                               else _bank_matmat)

            def run(d_full, d_rag, q, chip, key, vr, coef):
                nb = d_full.shape[0]
                if key is None:
                    code, volts = jax.vmap(
                        lambda db: core(db, q, p, chip, None, mode, vr))(
                        d_full)
                else:
                    code, volts = jax.vmap(
                        lambda db, bk: core(db, q, p, chip, bk, mode, vr))(
                        d_full, pl._fold_each(key, jnp.arange(nb)))
                if kind == "matvec":
                    code, volts = code.reshape(-1), volts.reshape(-1)
                else:
                    code, volts = pl._merge_banked(code, volts)
                if d_rag is not None:
                    rk = (None if key is None
                          else jax.random.fold_in(key, nb))
                    rc, rv = core(d_rag, q, p, chip, rk, mode, vr)
                    axis = 0 if kind == "matvec" else 1
                    code = jnp.concatenate([code, rc], axis)
                    volts = jnp.concatenate([volts, rv], axis)
                if coef is None:
                    return code, volts
                # fused calibration epilogue: once over the merged codes,
                # inside the same jit — the dispatch count stays 1
                qs = jnp.asarray(q).astype(jnp.float32).sum(-1)
                if kind != "matvec":
                    qs = qs[:, None]
                return code, volts, pl.trim_epilogue(code, qs, coef, p, vr,
                                                     mode)

            self._jit[k] = jax.jit(run)
        return self._jit[k]

    def _fused_host(self, kind, stored, q, mode, key, v_range,
                    trim=None) -> DimaOut:
        m, n = stored.shape
        with _span("dima.prep"):
            rows_per, n_full, ragged = self._bank_split(m)
            d_full = stored[:n_full * rows_per].reshape(n_full, rows_per, n)
            d_rag = stored[n_full * rows_per:] if ragged else None
            fn, coef = self._fused_fn(kind, mode), _trim_coef(trim)
        with _span("dima.launch"):
            out = _dispatch(lambda: fn(d_full, d_rag, q, self.chip, key,
                                       v_range, coef))
        with _span("dima.assemble"):
            n_ops = m if kind == "matvec" else q.shape[0] * m
            return DimaOut(out[0], out[1],
                           n_ops * pl._cycles_per_op(n, self.p), n_ops,
                           out[2] if len(out) == 3 else None)

    # -- fused pallas path: the banked kernel grid --------------------------

    def _fused_pallas(self, kind, stored, q, mode, key, v_range,
                      trim=None) -> DimaOut:
        from repro.kernels import ops as kops
        self.inner._require_kernel_mode(mode)
        m, n = stored.shape
        matvec = kind == "matvec"
        with _span("dima.prep"):
            rows_per, n_full, ragged = self._bank_split(m)
        with _span("dima.launch"):
            out = _dispatch(lambda: kops._bank_call(
                stored, q, self.chip, key, v_range, trim, 0, params=self.p,
                interpret=self.inner.interpret, mode=mode, matvec=matvec,
                rows_per=rows_per))
        with _span("dima.assemble"):
            code, volts = out[0], out[1]
            trimmed = out[2] if len(out) == 3 else None
            if ragged:
                # separate launch: the ragged bank's padded row count —
                # and with it the noise-array shapes — differs from the
                # full banks', and the counter-based PRNG is not
                # prefix-stable
                op = self.inner.matvec if matvec else self.inner.matmat
                out_r = op(stored[n_full * rows_per:], q, mode=mode,
                           key=self._bank_key(key, n_full), v_range=v_range,
                           trim=trim)
                axis = 0 if matvec else 1
                code = jnp.concatenate([code, out_r.code], axis)
                volts = jnp.concatenate([volts, out_r.volts], axis)
                if trimmed is not None:
                    trimmed = jnp.concatenate([trimmed, out_r.trimmed], axis)
            n_ops = m if matvec else q.shape[0] * m
            return DimaOut(code, volts, n_ops * pl._cycles_per_op(n, self.p),
                           n_ops, trimmed)

    # -- device-mesh fan-out ------------------------------------------------

    def _mesh_banked(self, stored):
        """Validate the mesh/shape contract and stack rows on the bank
        axis: (m, n) -> (n_banks, rows_per, n)."""
        from repro.distributed.sharding import require_banks_axis
        require_banks_axis(self.mesh)
        nb = self.n_banks
        m, n = stored.shape
        if m % nb != 0:
            raise ValueError(
                f"mesh fan-out shards rows uniformly: m={m} must divide "
                f"into n_banks={nb} — pad stored rows or use the host "
                "path (mesh=None), which handles the ragged last bank")
        if nb % self.mesh.shape["banks"] != 0:
            raise ValueError(
                f"n_banks={nb} must be a multiple of the mesh 'banks' "
                f"axis size {self.mesh.shape['banks']}")
        return stored.reshape(nb, m // nb, n)

    def _mesh_fn(self, kind, mode, has_key, has_vr, has_trim):
        """The cached jitted shard_map over the bank axis; cached per
        (inner, op, mode, key/v_range/trim presence) like ``_fused_fn``
        so repeated mesh calls re-execute instead of re-tracing the whole
        per-bank pipeline.  ``key``/``v_range``/``trim`` are replicated
        *operands* (dummy zeros when absent — dead code under jit), and
        bank ids resume where the previous shard stopped, so fold_in
        streams match the host path bank-for-bank.

        With a ``reference`` inner each shard vmaps the SAME per-bank
        core as the host fused path; with a ``pallas`` inner each shard
        is ONE banked kernel launch (kernels/ops.py ``*_bank_*`` with
        ``bank_offset = axis_index * local_banks``) — the kernel-only
        device path, codes bitwise equal to the host fused Pallas path
        (which stays the oracle; volts/trimmed to float-assembly
        tolerance)."""
        from jax.sharding import PartitionSpec
        _check_mode(mode)
        pallas_inner = isinstance(self.inner, PallasBackend)
        k = ("mesh", self.inner.name, kind, mode, has_key, has_vr, has_trim)
        if k not in self._jit:
            p, chip = self.p, self.chip

            if pallas_inner:
                from repro.kernels import ops as kops
                self.inner._require_kernel_mode(mode)
                kf = {("matvec", "dp"): kops.dima_dp_bank_matvec,
                      ("matvec", "md"): kops.dima_md_bank_matvec,
                      ("matmat", "dp"): kops.dima_dp_bank_matmat,
                      ("matmat", "md"): kops.dima_md_bank_matmat}[
                    (kind, mode)]
                interp = self.inner.interpret

                def per_shard(d_blk, q, key, vr, ep):
                    start = jax.lax.axis_index("banks") * d_blk.shape[0]
                    return kf(d_blk, q, p, chip,
                              key if has_key else None,
                              (vr[0], vr[1]) if has_vr else None,
                              interpret=interp,
                              trim=ep if has_trim else None,
                              bank_offset=start)
            else:
                core = _bank_matvec if kind == "matvec" else _bank_matmat

                def per_shard(d_blk, q, key, vr, ep):
                    start = jax.lax.axis_index("banks") * d_blk.shape[0]
                    vrange = (vr[0], vr[1]) if has_vr else None

                    def one_bank(i, d_b):
                        kk = (jax.random.fold_in(key, start + i) if has_key
                              else None)
                        return core(d_b, q, p, chip, kk, mode, vrange)

                    code, volts = jax.vmap(one_bank)(
                        jnp.arange(d_blk.shape[0]), d_blk)
                    if not has_trim:
                        return code, volts
                    qs = jnp.asarray(q).astype(jnp.float32).sum(-1)
                    if kind != "matvec":
                        qs = qs[:, None]    # broadcasts over the bank axis
                    return code, volts, pl.trim_epilogue(code, qs, ep, p,
                                                         vrange, mode)

            n_out = 3 if has_trim else 2
            self._jit[k] = jax.jit(jax.shard_map(
                per_shard, mesh=self.mesh,
                in_specs=(PartitionSpec("banks"), PartitionSpec(),
                          PartitionSpec(), PartitionSpec(),
                          PartitionSpec()),
                out_specs=(PartitionSpec("banks"),) * n_out,
                check_vma=False))
        return self._jit[k]

    def _mesh_call(self, kind, stored, q, mode, key, v_range, trim):
        """Stack the banks and run the shard_map'd function once: the
        ``dima.prep`` and ``dima.launch`` spans of a mesh call."""
        with _span("dima.prep"):
            banked = self._mesh_banked(stored)
            f = self._mesh_fn(kind, mode, key is not None,
                              v_range is not None, trim is not None)
            key_op = (jnp.zeros((2,), jnp.uint32) if key is None
                      else key)
            vr_op = (jnp.zeros((2,), jnp.float32) if v_range is None
                     else jnp.asarray(v_range, jnp.float32))
            ep_op = (jnp.zeros((3,), jnp.float32) if trim is None
                     else _trim_coef(trim))
        with _span("dima.launch"):
            return _dispatch(lambda: f(banked, q, key_op, vr_op, ep_op))

    def _matvec_mesh(self, stored, query, mode, key, v_range,
                     trim=None) -> DimaOut:
        m, n = stored.shape
        out = self._mesh_call("matvec", stored, query, mode, key, v_range,
                              trim)
        with _span("dima.assemble"):
            trimmed = out[2].reshape(m) if len(out) == 3 else None
            return DimaOut(out[0].reshape(m), out[1].reshape(m),
                           m * pl._cycles_per_op(n, self.p), m, trimmed)

    def _matmat_mesh(self, stored, queries, mode, key, v_range,
                     trim=None) -> DimaOut:
        m, n = stored.shape
        b = queries.shape[0]
        out = self._mesh_call("matmat", stored, queries, mode, key, v_range,
                              trim)
        with _span("dima.assemble"):
            code, volts, *trimmed = pl._merge_banked(*out)
            trimmed = trimmed[0] if trimmed else None
            return DimaOut(code, volts, b * m * pl._cycles_per_op(n, self.p),
                           b * m, trimmed)

    # -- cost ---------------------------------------------------------------

    @property
    def bank_fixed_pj(self) -> float:
        """Per-bank share of the fixed CTRL energy (the merge path's
        per-conversion charge)."""
        return energy_mod.bank_fixed_split(self.p, self.n_banks)

    def decision_cost(self, n_dims: int, *, mode="dp", n_ops=1,
                      multi_bank=True, **kw) -> energy_mod.Cost:
        """Always the amortized model: this substrate *executes* banked,
        so the fixed CTRL energy splits over its ``n_banks``."""
        return energy_mod.dima_decision(self.p, n_dims, mode=mode,
                                        n_ops=n_ops, multi_bank=True,
                                        n_banks=self.n_banks, **kw)


# ---------------------------------------------------------------------------
# auto: per-call dispatch
# ---------------------------------------------------------------------------

_MIN_ROWS_DEFAULT = 128
# the bench artifact lives at the repo root (src/repro/core/ -> three up),
# NOT in the process CWD — dispatch must not change with the launch
# directory; absent in an installed package -> static fallback
_BENCH_JSON = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "BENCH_dima_api.json"))


# "pallas never wins" threshold: larger than any real stored-row count,
# so AutoBackend keeps everything on the reference path
_MIN_ROWS_NEVER = 1 << 62


def measured_min_rows(path: str = None) -> Optional[int]:
    """The reference↔pallas crossover measured by ``benchmarks/run.py``
    (repo-root BENCH_dima_api.json, override the path with
    $DIMA_BENCH_JSON).  None when no benchmark run has produced one for
    *this platform* — AutoBackend then falls back to the static default.
    The sentinel ``"never"`` means the sweep *measured* pallas losing at
    every relevant count — that returns an effectively infinite
    threshold, NOT the static fallback: 'measured: pallas never wins'
    must keep auto off the pallas path, while 'not measured' merely
    reverts to the default guess.

    The crossover is platform-specific — ``"never"`` on CPU is an
    interpret-mode artifact that says nothing about TPU/GPU — so the
    artifact's ``crossover`` section is keyed by ``jax.default_backend()``
    platform name::

        "crossover": {"cpu": {"rows": "never", ...},
                      "tpu": {"rows": 256, ...}}

    and only the entry matching the running platform is read.  Legacy
    flat artifacts (``auto_crossover_rows`` + ``auto_crossover_platform``
    tag) are still honored: a measurement tagged with a different
    platform than the running backend is ignored; untagged flat
    artifacts are trusted as-is."""
    path = path or os.environ.get("DIMA_BENCH_JSON", _BENCH_JSON)
    try:
        with open(path) as f:
            data = json.load(f)
        section = data.get("crossover")
        if isinstance(section, dict):
            entry = section.get(jax.default_backend())
            if entry is None:
                return None
            v = entry.get("rows") if isinstance(entry, dict) else entry
        else:                                   # legacy flat layout
            plat = data.get("auto_crossover_platform")
            if plat is not None and plat != jax.default_backend():
                return None
            v = data.get("auto_crossover_rows")
        if v == "never":
            return _MIN_ROWS_NEVER
        return int(v) if v else None
    except (OSError, ValueError, TypeError):
        return None


@register_backend("auto")
class AutoBackend(DimaBackend):
    """Dispatches each call to the cheapest capable substrate: the Pallas
    kernels for large banked batches (one query against ≥``min_rows``
    stored rows of ≤256 dims), the reference model otherwise.
    ``min_rows`` defaults to the measured crossover from the last
    benchmark run (``measured_min_rows``) when BENCH_dima_api.json is
    present, else 128."""

    def __init__(self, p: DimaParams = None, chip=None, min_rows: int = None):
        super().__init__(p, chip)
        if min_rows is None:
            min_rows = measured_min_rows() or _MIN_ROWS_DEFAULT
        self.min_rows = min_rows
        self.reference = ReferenceBackend(self.p, chip)
        self.pallas = PallasBackend(self.p, chip)

    def ideal(self) -> "AutoBackend":
        return AutoBackend(self.p, None, self.min_rows)

    def pick(self, stored, query, mode="dp") -> DimaBackend:
        stored = jnp.asarray(stored)
        query = jnp.asarray(query)
        if (mode in PallasBackend.KERNEL_MODES and stored.ndim == 2
                and query.ndim == 1
                and stored.shape[-1] <= self.p.dims_per_conversion
                and stored.shape[0] >= self.min_rows):
            return self.pallas
        return self.reference

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        return self.pick(stored, query, mode).dot(
            stored, query, mode=mode, key=key, v_range=v_range, trim=trim)

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        return self.pick(stored, query, mode).matvec(
            stored, query, mode=mode, key=key, v_range=v_range, trim=trim)

    def matmat(self, stored, queries, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        queries = jnp.asarray(queries)
        return self.pick(stored, queries[0], mode).matmat(
            stored, queries, mode=mode, key=key, v_range=v_range, trim=trim)


# ---------------------------------------------------------------------------
# bitserial: bit-scalable precision via per-plane analog ops
# ---------------------------------------------------------------------------

@register_backend("bitserial")
class BitSerialBackend(DimaBackend):
    """Bit-scalable precision: each stored 8-b word is split into
    ``n_planes`` bit planes (``quant/bitplanes.py``), every plane runs as
    its own analog op, and the per-plane results recombine by a shifted
    digital accumulate — the IMAC / bit-scalable-accelerator scheme on
    the DIMA substrate.

    ``n_planes=1`` *delegates verbatim* to the reference path: same jit,
    same key layout, bitwise-identical codes/volts including noisy runs —
    the paper-exact binary-word behavior.

    ``n_planes>1`` (default path) models the narrow-plane read with a
    *linear* bit-plane transfer: plane reads bypass the 4-b sub-range
    capacitive multiplier (a ``w = 8/B``-bit plane fits the BLP's linear
    range), so the per-plane partial result is the exact integer plane
    dot, optionally scaled by the chip's per-column gain and perturbed by
    conversion noise when a ``key`` is supplied.  The shifted accumulate
    ``sum_k 2**(k*w) * pd_k`` then *telescopes back to the exact 8-b
    result*: with an ideal chip at zero noise the output is bitwise equal
    to the ``digital`` backend (codes AND volts) for every valid B and
    every ``v_range``.  All planes ride a leading vmap axis inside ONE
    jitted computation — a multi-plane matvec is a single dispatch,
    guarded by ``count_dispatches``.

    ``physical=True`` instead pushes the planes through the banked fused
    Pallas kernels (kernels/ops.py: planes on the bank-leading grid axis,
    still one launch): the real nonlinear per-plane readout with an 8-b
    ADC per plane — lossy, for realism studies; dp mode only.

    MD mode at B>1 plane-splits the query too and accumulates per-plane
    Manhattan distances — an upper bound on the 8-b distance (exact at
    B=1), which is what makes precision an *accuracy* axis for TM/KNN in
    the Pareto sweep.

    Energy: ``decision_cost`` bills every plane's cycles + conversion
    with the ΔV discount of its reduced swing
    (``energy.bitserial_decision``); B=1 reduces exactly to
    ``dima_decision``.
    """

    def __init__(self, p: DimaParams = None, chip=None, n_planes: int = 1,
                 physical: bool = False, full_swing: bool = True,
                 interpret: bool = None, plane_v_range=None):
        super().__init__(p, chip)
        from repro.quant import bitplanes as bp_mod
        self._bp = bp_mod
        self.n_planes = int(n_planes)
        self.plane_bits = bp_mod.plane_width(self.n_planes)  # validates B
        self.physical = bool(physical)
        self.full_swing = bool(full_swing)
        self.interpret = interpret
        # per-plane ADC windows for the physical path: (n_planes, 2) f32,
        # e.g. calibration.calibrate_plane_range's data-driven windows;
        # None = the analytic worst-case calibration.plane_v_range
        self.plane_v_range = (
            None if plane_v_range is None
            else jnp.asarray(plane_v_range,
                             jnp.float32).reshape(self.n_planes, 2))
        self._ref = ReferenceBackend(self.p, chip)
        self._jit = {}

    def ideal(self) -> "BitSerialBackend":
        return BitSerialBackend(self.p, None, n_planes=self.n_planes,
                                physical=self.physical,
                                full_swing=self.full_swing,
                                interpret=self.interpret,
                                plane_v_range=self.plane_v_range)

    # -- the linear multi-plane core (one traced computation) ---------------

    def _gain(self, mode):
        return pl.dp_gain(self.p) if mode == "dp" else pl.md_gain(self.p)

    def _default_range(self, mode):
        full = 255.0 * 255.0 if mode == "dp" else 255.0
        return (0.0, full * self._gain(mode))

    def _sigma_pd(self, mode):
        """Per-plane conversion noise referred to the digital (pd)
        domain: the BL read noise of the two access cycles plus the CBLP
        charge-share noise, divided by the transfer gain.  The noise is
        constant in *volts*; what it costs in pd counts depends on the
        plane's readout swing:

        * ``full_swing=True``: the conversion is amplified to the full
          range, so the plane's reduced numeric range maps onto the same
          volts — plane-referred noise shrinks by ``plane_scale`` (the
          standard bit-serial arrangement, billed at full cycle energy);
        * ``full_swing=False``: the plane keeps its native per-bit swing
          — cheaper cycles (``bitserial_decision``), but constant noise
          now eats a ``1/plane_scale`` larger share of the shrunken
          signal, and the shifted accumulate amplifies the MSB planes'
          errors.  The cheap/noisy end of the precision knob.
        """
        p = self.p
        var = 2.0 * (p.sigma_read_mv * 1e-3) ** 2 \
            + (p.sigma_cblp_mv * 1e-3) ** 2
        sigma = float(np.sqrt(var)) * p.dims_per_conversion \
            / self._gain(mode)
        if self.full_swing:
            sigma *= self._bp.plane_scale(self.n_planes)
        return sigma

    def _plane_core(self, stored, query, mode, chip, key, v_range):
        """Traced: (B planes as a leading axis) -> final code/volts."""
        p, B, w = self.p, self.n_planes, self.plane_bits
        d = jnp.asarray(stored, jnp.int32)
        q = jnp.asarray(query, jnp.int32)
        d, q = jnp.broadcast_arrays(d, q)
        shifts = (w * jnp.arange(B, dtype=jnp.int32)) \
            .reshape((B,) + (1,) * d.ndim)
        mask = (1 << w) - 1
        planes_d = (d[None, ...] >> shifts) & mask
        if mode == "dp":
            elem = planes_d * q                      # (B, ..., n) int32
        else:
            planes_q = (q[None, ...] >> shifts) & mask
            elem = jnp.abs(planes_d - planes_q)
        if chip is not None:
            # narrow-plane reads bypass the sub-range multiplier; the
            # per-column BLP gain is the surviving fixed-pattern term
            n = elem.shape[-1]
            col = chip["col_gain"][jnp.arange(n) % p.words_per_access]
            pd = jnp.sum(elem.astype(jnp.float32) * col, axis=-1)
        else:
            pd = jnp.sum(elem, axis=-1)              # exact int32
        if key is not None:
            pd = pd + self._sigma_pd(mode) * jax.random.normal(key, pd.shape)
        wts = (2 ** (w * jnp.arange(B))).astype(pd.dtype) \
            .reshape((B,) + (1,) * (pd.ndim - 1))
        acc = jnp.sum(pd * wts, axis=0)
        # final transfer/ADC: literally DigitalBackend's arithmetic, so
        # the exact path is bitwise-comparable to the digital backend
        v = acc.astype(jnp.float32) / p.dims_per_conversion \
            * self._gain(mode)
        if v_range is None:
            v_range = self._default_range(mode)
        code = adc_mod.adc(v, v_range[0], v_range[1], p)
        return code, v

    def _fn(self, kind, mode):
        _check_mode(mode)
        k = (kind, mode)
        if k not in self._jit:
            p = self.p

            def _with_trim(code, volts, q, vr, coef, per_query):
                if coef is None:
                    return code, volts
                qs = jnp.asarray(q).astype(jnp.float32).sum(-1)
                if per_query:
                    qs = qs[:, None]
                return code, volts, pl.trim_epilogue(code, qs, coef, p, vr,
                                                     mode)

            if kind == "matmat":
                def run(s, qs, chip, key, vr, coef):
                    if key is None:
                        code, volts = jax.vmap(lambda q: self._plane_core(
                            s, q, mode, chip, None, vr))(qs)
                    else:
                        keys = jax.random.split(key, qs.shape[0])
                        code, volts = jax.vmap(lambda q, kk: self._plane_core(
                            s, q, mode, chip, kk, vr))(qs, keys)
                    return _with_trim(code, volts, qs, vr, coef, True)
                self._jit[k] = jax.jit(run)
            else:
                def run(s, q, chip, key, vr, coef):
                    code, volts = self._plane_core(s, q, mode, chip, key, vr)
                    return _with_trim(code, volts, q, vr, coef, False)
                self._jit[k] = jax.jit(run)
        return self._jit[k]

    # -- physical per-plane readout (planes on the bank-leading grid) -------

    def _physical_fn(self, kind):
        """The physical path's one jitted body: plane kernel launch →
        per-plane decode (each plane against its OWN ADC window row) →
        shifted accumulate → re-ADC, plus the optional fused trim
        epilogue — plane merge and epilogue ride the kernel dispatch
        instead of separate XLA ops per call."""
        k = ("physical", kind)
        if k not in self._jit:
            from repro.kernels import ops as ops_mod
            p, B, w = self.p, self.n_planes, self.plane_bits
            per = p.dims_per_conversion
            gain = self._gain("dp")
            interpret = self.interpret
            f = (ops_mod.dima_dp_plane_matvec if kind == "matvec"
                 else ops_mod.dima_dp_plane_matmat)

            def run(planes, q, chip, key, pvr, vr, coef):
                codes, _ = f(planes, q, p, chip, key, pvr,
                             interpret=interpret)    # (B, [b,] m)
                # per-plane decode: window row k decodes plane k (a (B,2)
                # pvr cannot go through pl.code_to_dot, whose v_range is
                # one scalar pair — broadcast the rows explicitly)
                full = float(2 ** p.adc_bits - 1)
                shape = (B,) + (1,) * (codes.ndim - 1)
                lo = pvr[:, 0].reshape(shape)
                hi = pvr[:, 1].reshape(shape)
                vd = lo + codes.astype(jnp.float32) / full * (hi - lo)
                pd = vd / gain * per
                wts = (2.0 ** (w * jnp.arange(B))).reshape(shape)
                acc = jnp.sum(pd * wts, axis=0)
                v = acc.astype(jnp.float32) / per * gain
                code = adc_mod.adc(v, vr[0], vr[1], p)
                if coef is None:
                    return code, v
                qs = jnp.asarray(q).astype(jnp.float32).sum(-1)
                if kind != "matvec":
                    qs = qs[:, None]
                return code, v, pl.trim_epilogue(code, qs, coef, p,
                                                 (vr[0], vr[1]), "dp")

            self._jit[k] = jax.jit(run)
        return self._jit[k]

    def _physical_matop(self, kind, stored, q, mode, key, v_range,
                        trim=None):
        from repro.core import calibration as cal_mod
        if mode != "dp":
            raise NotImplementedError(
                "physical bitserial planes ride the dp bank kernels; "
                "md needs a plane-split query per plane")
        p, B = self.p, self.n_planes
        stored = jnp.asarray(stored, jnp.uint8)
        per = p.dims_per_conversion
        pad = per - stored.shape[-1]
        q = jnp.asarray(q, jnp.uint8)
        if pad:
            stored = jnp.pad(stored, [(0, 0)] * (stored.ndim - 1) + [(0, pad)])
            q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
        planes = self._bp.split_planes(stored, B)    # (B, m, 256)
        pvr = self.plane_v_range
        if pvr is None:
            lo, hi = cal_mod.plane_v_range(p, mode=mode, n_planes=B)
            pvr = jnp.broadcast_to(
                jnp.asarray([lo, hi], jnp.float32), (B, 2))
        vr = jnp.asarray(self._default_range(mode) if v_range is None
                         else v_range, jnp.float32)
        return _dispatch(lambda: self._physical_fn(kind)(
            planes, q, self.chip, key, pvr, vr, _trim_coef(trim)))

    # -- the one signature --------------------------------------------------

    def dot(self, stored, query, *, mode="dp", key=None,
            v_range=None, trim=None) -> DimaOut:
        if self.n_planes == 1:
            return self._ref.dot(stored, query, mode=mode, key=key,
                                 v_range=v_range, trim=trim)
        stored = jnp.asarray(stored)
        query = jnp.asarray(query)
        n = max(stored.shape[-1], query.shape[-1])
        _check_op_dims(n, self.p)
        out = _dispatch(lambda: self._fn("op", mode)(
            stored, query, self.chip, key, v_range, _trim_coef(trim)))
        return DimaOut(out[0], out[1],
                       self.n_planes * pl._cycles_per_op(n, self.p),
                       self.n_planes, out[2] if len(out) == 3 else None)

    def matvec(self, stored, query, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        if self.n_planes == 1:
            return self._ref.matvec(stored, query, mode=mode, key=key,
                                    v_range=v_range, trim=trim)
        stored = jnp.asarray(stored)
        m = stored.shape[0]
        _check_op_dims(stored.shape[-1], self.p)
        if self.physical:
            out = self._physical_matop("matvec", stored, query, mode, key,
                                       v_range, trim)
        else:
            out = _dispatch(lambda: self._fn("matvec", mode)(
                stored, jnp.asarray(query), self.chip, key, v_range,
                _trim_coef(trim)))
        cyc = pl._cycles_per_op(stored.shape[-1], self.p)
        return DimaOut(out[0], out[1], m * self.n_planes * cyc,
                       m * self.n_planes,
                       out[2] if len(out) == 3 else None)

    def matmat(self, stored, queries, *, mode="dp", key=None,
               v_range=None, trim=None) -> DimaOut:
        if self.n_planes == 1:
            return self._ref.matmat(stored, queries, mode=mode, key=key,
                                    v_range=v_range, trim=trim)
        stored = jnp.asarray(stored)
        queries = jnp.asarray(queries)
        b, m = queries.shape[0], stored.shape[0]
        _check_op_dims(stored.shape[-1], self.p)
        if self.physical:
            out = self._physical_matop("matmat", stored, queries, mode, key,
                                       v_range, trim)
        else:
            out = _dispatch(lambda: self._fn("matmat", mode)(
                stored, queries, self.chip, key, v_range, _trim_coef(trim)))
        cyc = pl._cycles_per_op(stored.shape[-1], self.p)
        return DimaOut(out[0], out[1], b * m * self.n_planes * cyc,
                       b * m * self.n_planes,
                       out[2] if len(out) == 3 else None)

    def decision_cost(self, n_dims: int, *, mode="dp", n_ops=1,
                      multi_bank=False, **kw) -> energy_mod.Cost:
        kw.setdefault("full_swing", self.full_swing)
        return energy_mod.bitserial_decision(
            self.p, n_dims, mode=mode, n_planes=self.n_planes,
            n_ops=n_ops, multi_bank=multi_bank, **kw)


# ---------------------------------------------------------------------------
# helpers shared by the applications / serving layers
# ---------------------------------------------------------------------------

def iter_chunks(n: int, per: int):
    """(start, stop) segments of one conversion each — the single place
    conversion chunking is defined (shared with core.calibration)."""
    for a in range(0, n, per):
        yield a, min(a + per, n)


def _chunk_stack(x, n_chunks, per):
    """(..., n) -> (n_chunks, ..., per): zero-pad the trailing dim to
    ``n_chunks·per`` and move the chunk axis to the front.  Zero padding
    is exactly what ``pipeline._pad_to_conversion`` does to the loop's
    ragged last chunk, so values are identical chunk-for-chunk."""
    n = x.shape[-1]
    if n < n_chunks * per:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n_chunks * per - n)])
    x = x.reshape(x.shape[:-1] + (n_chunks, per))
    return jnp.moveaxis(x, -2, 0)


def chunked_dot(backend: DimaBackend, stored, query, *, mode="dp", key=None,
                v_range=None):
    """>256-dim op: one ADC conversion per ``dims_per_conversion`` segment,
    decoded codes summed digitally — the prototype's dataflow for long
    vectors (e.g. the SVM's 506-dim feature).  Per-chunk keys are
    ``fold_in(key, chunk_index)`` (via the vmap-invariant ``_fold_each``).
    Returns the decoded total (float).

    All conversions execute as ONE dispatch: chunks are stacked on a
    leading axis and ``backend.dot`` is vmapped over them inside a
    per-mode jit cached on the backend instance.  The per-chunk decode +
    digital sum stay *eager* on the returned codes — the same primitive
    sequence as the loop — so the total is bit-for-bit identical to the
    seed's per-chunk Python loop, which ``chunked_dot_loop`` keeps as
    the test oracle (decoding inside the jit would let XLA fuse the
    dac/sum chain and drift the float32 total by 1 ulp)."""
    stored = jnp.asarray(stored)
    query = jnp.asarray(query)
    n = max(stored.shape[-1], query.shape[-1])
    per = backend.p.dims_per_conversion
    n_chunks = -(-n // per)
    cache = backend.__dict__.setdefault("_chunked_jit", {})
    if mode not in cache:
        def run(s_c, q_c, key, vr):
            def one(s, q, k):
                return backend.dot(s, q, mode=mode, key=k, v_range=vr).code
            if key is None:
                return jax.vmap(lambda s, q: one(s, q, None))(s_c, q_c)
            return jax.vmap(one)(s_c, q_c,
                                 pl._fold_each(key,
                                               jnp.arange(s_c.shape[0])))
        cache[mode] = jax.jit(run)
    codes = _dispatch(lambda: cache[mode](
        _chunk_stack(stored, n_chunks, per), _chunk_stack(query, n_chunks,
                                                          per),
        key, v_range))
    total = 0.0
    for i in range(n_chunks):
        total = total + backend.decode(codes[i], mode=mode, v_range=v_range)
    return total


def chunked_dot_loop(backend: DimaBackend, stored, query, *, mode="dp",
                     key=None, v_range=None):
    """The seed's per-chunk Python loop (one ``backend.dot`` dispatch per
    segment).  Kept as the oracle the vectorized ``chunked_dot`` is
    tested bit-for-bit against, and as the benchmark baseline."""
    stored = jnp.asarray(stored)
    query = jnp.asarray(query)
    n = max(stored.shape[-1], query.shape[-1])
    total = 0.0
    for i, (a, b) in enumerate(iter_chunks(n, backend.p.dims_per_conversion)):
        k = None if key is None else jax.random.fold_in(key, i)
        out = backend.dot(stored[..., a:b], query[..., a:b], mode=mode,
                          key=k, v_range=v_range)
        total = total + backend.decode(out.code, mode=mode, v_range=v_range)
    return total


def weights_energy_per_token(n_active: int, backend: DimaBackend = None,
                             *, multi_bank: bool = None):
    """Modeled energy to stream ``n_active`` 8-b weights through the
    backend once (one decode token): every weight byte is read through
    MR-FR banks as 256-dim DP conversions.  Returns (pJ, n_banks).

    ``multi_bank=None`` switches on what the backend *executes*: the
    amortized CTRL model for ``multibank`` (which forces it regardless),
    the single-bank model for the other analog substrates, and the
    conventional fetch-then-compute model for ``digital`` (which ignores
    the flag).  Pass an explicit bool to model a what-if."""
    from repro.core import mapping as mapping_mod
    if backend is None:
        backend = get_backend("reference")
    if multi_bank is None:
        multi_bank = backend.executes_multibank
    per = backend.p.dims_per_conversion
    c = backend.decision_cost(per, mode="dp", n_ops=int(n_active / per),
                              multi_bank=multi_bank)
    banks = mapping_mod.banks_for_matrix((n_active,), bits=8, p=backend.p)
    return c.energy_pj, banks
