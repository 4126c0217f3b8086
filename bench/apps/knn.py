"""k-NN over stored digit exemplars (MD mode), the paper's k-NN path.

Each bank holds one candidate set: the application's 64 exemplars, 16
per class.  A query's Manhattan distance to every stored exemplar comes
back as an ADC code, and each candidate set gives one decision: the
majority class of its k nearest codes (ties to the lower index, then the
lower class).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.apps.mf import ideal_range
from bench.traffic import generators

MODE = "md"
TRIM = False
K = 5


def make_data(key, n_stored, n_queries, rows_per_decision):
    return generators.digits(key, n_stored=n_stored, n_queries=n_queries,
                             per_set=rows_per_decision)


def calibrate(stored, stored_labels, cal_queries, cal_labels, chip, key,
              cfg):
    """ADC range from the noise-free ideal chain; no trim."""
    if cfg["k"] != K or cfg["classes"] != generators.N_CLASSES:
        raise ValueError(f"the k-NN app votes {K} neighbours over "
                         f"{generators.N_CLASSES} classes")
    labels = stored_labels.reshape(-1, cfg["rows_per_decision"])
    return (ideal_range(stored, cal_queries, cfg, MODE), np.zeros(3),
            {"set_labels": labels})


def decide(code, trimmed, aux):
    """The voted class per query and candidate set, int32."""
    labels = aux["set_labels"]                          # (sets, rows)
    code = code.reshape(code.shape[:-1] + labels.shape)
    _, idx = jax.lax.top_k(-code, K)
    near = jnp.take_along_axis(jnp.broadcast_to(labels, code.shape), idx,
                               axis=-1)
    votes = jax.nn.one_hot(near, generators.N_CLASSES,
                           dtype=jnp.int32).sum(-2)
    return jnp.argmax(votes, axis=-1).astype(jnp.int32)
