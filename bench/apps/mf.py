"""Matched filter over a template bank (DP mode), the paper's MF path.

Each stored row is one template, and each (window, template) pair is
one decision, as in the paper's MF application: one 256-sample dot
product through the analog chain and the fused affine trim, detected
when the trimmed score passes half the template's energy (the midpoint
between the expected correlation of a window that holds the template
and of one that does not).  Templates and windows are 8-b offset-binary
signals, so the trim maps the decoded dot product and the window's sum
onto the signed correlation the digital reference computes.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.traffic import generators

MODE = "dp"
TRIM = True


def make_data(key, n_stored, n_queries, rows_per_decision):
    return generators.gunshots(key, n_stored=n_stored, n_queries=n_queries)


def calibrate(stored, stored_labels, cal_queries, cal_labels, chip, key,
              cfg):
    """ADC range from the noise-free ideal chain, affine trim fitted on
    the noisy chain of this chip, and each template's threshold at half
    its energy."""
    if cfg["rows_per_decision"] != 1:
        raise ValueError("a matched-filter decision is one template")
    prm = cfg["params"]
    v_range = ideal_range(stored, cal_queries, cfg, MODE)
    code, _, _ = reference.run(MODE, stored, cal_queries, chip, key,
                               v_range, np.zeros(3), prm,
                               n_banks=cfg["n_banks"])
    code = np.asarray(code, np.float64)
    lo, hi = v_range
    dot_hat = ((lo + code / (2 ** prm["adc_bits"] - 1) * (hi - lo))
               / reference.dp_gain(prm) * reference.DIMS)
    q_sum = np.broadcast_to(
        np.asarray(cal_queries, np.float64).sum(-1)[:, None], code.shape)
    corr = np.asarray(reference.digital_scores(MODE, stored, cal_queries),
                      np.float64)
    coef = reference.affine_trim(dot_hat, q_sum, corr)
    t = np.asarray(stored, np.float64) - 128.0
    thr = 0.5 * (t * t).sum(-1)
    return v_range, coef, {"threshold": jnp.asarray(thr, jnp.float32)}


def decide(code, trimmed, aux):
    """Detected (1) or not (0) per window and template, int32."""
    return (trimmed >= aux["threshold"]).astype(jnp.int32)


def ideal_range(stored, cal_queries, cfg, mode, margin=0.05):
    """ADC window (lo, hi) in volts: the swing of the ideal chain (no
    mismatch, no noise) over the calibration set, with headroom."""
    _, volts, _ = reference.run(mode, stored, cal_queries,
                                reference.ideal_chip(), None, (0.0, 1.0),
                                np.zeros(3), cfg["params"],
                                n_banks=cfg["n_banks"])
    lo = float(jnp.min(volts))
    hi = float(jnp.max(volts))
    span = max(hi - lo, 1e-9)
    return (lo - margin * span, hi + margin * span)
