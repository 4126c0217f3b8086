"""The benchmark's data: stored vectors and query pools, made on the
device from the seed in one jitted call each.

Copied from the program's synthetic stand-ins for the paper's data sets
(procedural 16x16 digit glyphs for k-NN; a damped broadband transient in
white noise for the matched filter), vectorized with ``jax.random`` so
that thousands of exemplars cost one device call instead of a Python loop.  The
benchmark keeps its own copy so that no later change to the program can
move the yardstick.  Every function is a pure function of its key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# k-NN digits 0-3: 16x16 glyphs of blurred strokes
# ---------------------------------------------------------------------------

#: strokes (y0, x0, y1, x1) of each digit on a 16x16 canvas
SEGS = {
    0: [(2, 4, 2, 11), (13, 4, 13, 11), (2, 4, 13, 4), (2, 11, 13, 11)],
    1: [(2, 8, 13, 8), (2, 8, 4, 6)],
    2: [(2, 4, 2, 11), (2, 11, 7, 11), (7, 4, 7, 11), (7, 4, 13, 4),
        (13, 4, 13, 11)],
    3: [(2, 4, 2, 11), (7, 5, 7, 11), (13, 4, 13, 11), (2, 11, 13, 11)],
}
N_CLASSES = len(SEGS)
_MAX_SEGS = max(len(s) for s in SEGS.values())
_MAX_PTS = 34                       # 3 * longest stroke (11) + 1


def _stroke_table():
    """(classes, segs, 4) stroke ends, (classes, segs) point counts."""
    ends = np.zeros((N_CLASSES, _MAX_SEGS, 4), np.float32)
    npts = np.zeros((N_CLASSES, _MAX_SEGS), np.int32)
    for c, segs in SEGS.items():
        for s, (y0, x0, y1, x1) in enumerate(segs):
            ends[c, s] = (y0, x0, y1, x1)
            npts[c, s] = max(abs(y1 - y0), abs(x1 - x0)) * 3 + 1
    return ends, npts


def _to_u8(x):
    x = x - x.min()
    x = x / jnp.maximum(x.max(), 1e-9)
    return jnp.clip(jnp.round(x * 255), 0, 255).astype(jnp.uint8)


def draw_digit(key, digit):
    """One glyph (256,) uint8.  The vertical shift (p = 0.15 each way) is
    the difficulty knob of L1 matching; strokes jitter as a random walk,
    thickness varies, and pixel noise is added before 8-b scaling."""
    ends, npts = (jnp.asarray(a) for a in _stroke_table())
    k_dy, k_dx, k_th, k_wy, k_wx, k_px = jax.random.split(key, 6)
    dy = jax.random.choice(k_dy, jnp.asarray([-1.0, 0.0, 1.0]),
                           p=jnp.asarray([0.15, 0.70, 0.15]))
    dx = jax.random.randint(k_dx, (), -1, 2).astype(jnp.float32)
    thick = jax.random.uniform(k_th, (), minval=1.05, maxval=1.3)
    e = ends[digit]                                     # (S, 4)
    n = npts[digit]                                     # (S,)
    i = jnp.arange(_MAX_PTS, dtype=jnp.float32)
    t = i[None, :] / jnp.maximum(n[:, None] - 1, 1)    # (S, P)
    walk = lambda k: jnp.cumsum(
        jax.random.normal(k, (_MAX_SEGS, _MAX_PTS)) * 0.06, axis=1) * 0.2
    ys = e[:, :1] + (e[:, 2:3] - e[:, :1]) * t + dy + walk(k_wy)
    xs = e[:, 1:2] + (e[:, 3:4] - e[:, 1:2]) * t + dx + walk(k_wx)
    live = (i[None, :] < n[:, None]).astype(jnp.float32)  # pad segs: n = 0
    yy, xx = jnp.mgrid[0:16, 0:16].astype(jnp.float32)
    d2 = ((yy[None, None] - ys[..., None, None]) ** 2
          + (xx[None, None] - xs[..., None, None]) ** 2)
    img = (live[..., None, None]
           * jnp.exp(-d2 / (2 * (thick * 0.5) ** 2))).sum((0, 1))
    img = img / jnp.maximum(img.max(), 1e-9)
    img = img + jax.random.normal(k_px, (16, 16)) * 0.03
    return _to_u8(img).reshape(-1)


@functools.partial(jax.jit,
                   static_argnames=("n_stored", "n_queries", "per_set"))
def digits(key, n_stored, n_queries, per_set):
    """Stored exemplars in candidate sets of ``per_set`` consecutive
    rows, each set class-balanced in contiguous blocks (row ``i`` is
    class ``(i % per_set) * 4 // per_set``), and a pool of fresh queries
    of uniformly drawn classes.  Every exemplar is its own draw.  Returns
    (stored (n_stored, 256) uint8, stored labels, queries (n_queries,
    256) uint8, query labels)."""
    k_s, k_q, k_c = jax.random.split(key, 3)
    s_lab = ((jnp.arange(n_stored) % per_set) * N_CLASSES) // per_set
    q_lab = jax.random.randint(k_c, (n_queries,), 0, N_CLASSES)
    stored = jax.vmap(draw_digit)(jax.random.split(k_s, n_stored), s_lab)
    queries = jax.vmap(draw_digit)(jax.random.split(k_q, n_queries), q_lab)
    return stored, s_lab, queries, q_lab


# ---------------------------------------------------------------------------
# matched filter: a bank of gun-shot templates, 256 8-b samples each
# ---------------------------------------------------------------------------

QUANT_LO, QUANT_HI = -4.0, 4.0      # one fixed 8-b quantizer for all signals
SNR_DB = 3.0


def _quantize(x):
    return jnp.clip(jnp.round((x - QUANT_LO) / (QUANT_HI - QUANT_LO) * 255),
                    0, 255).astype(jnp.uint8)


def transient(key, n=256):
    """A damped broadband transient (muzzle blast-like) at unit RMS; the
    decay and the two carrier tones vary from template to template."""
    k_tau, k_f1, k_f2, k_ph, k_n = jax.random.split(key, 5)
    t = jnp.arange(n, dtype=jnp.float32)
    tau = jax.random.uniform(k_tau, (), minval=40.0, maxval=80.0)
    f1 = jax.random.uniform(k_f1, (), minval=0.08, maxval=0.14)
    f2 = jax.random.uniform(k_f2, (), minval=0.18, maxval=0.28)
    ph = jax.random.uniform(k_ph, (), maxval=2 * np.pi)
    carrier = jnp.sin(2 * np.pi * f1 * t) + 0.5 * jnp.sin(2 * np.pi * f2 * t
                                                          + ph)
    s = jnp.exp(-t / tau) * (carrier + 0.3 * jax.random.normal(k_n, (n,)))
    return s / jnp.sqrt(jnp.mean(s ** 2))


@functools.partial(jax.jit, static_argnames=("n_stored", "n_queries"))
def gunshots(key, n_stored, n_queries):
    """A template bank and a pool of sensor windows: even windows hold a
    drawn template in white noise at 3 dB SNR, odd windows noise of the
    same total power.  Returns (templates (n_stored, 256) uint8, template
    ids, windows (n_queries, 256) uint8, labels: the template id of a
    signal window, -1 for noise)."""
    k_t, k_pick, k_noise = jax.random.split(key, 3)
    bank = jax.vmap(transient)(jax.random.split(k_t, n_stored))
    noise_pow = 1.0 / (10 ** (SNR_DB / 10))
    pick = jax.random.randint(k_pick, (n_queries,), 0, n_stored)
    signal = (jnp.arange(n_queries) % 2) == 0
    w = jax.random.normal(k_noise, (n_queries, 256))
    x = jnp.where(signal[:, None], bank[pick] + w * jnp.sqrt(noise_pow),
                  w * jnp.sqrt(1.0 + noise_pow))
    labels = jnp.where(signal, pick, -1)
    return (_quantize(bank), jnp.arange(n_stored), _quantize(x), labels)
