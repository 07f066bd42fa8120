"""Plain jax.numpy client models of the benchmark's configurations.

A configuration names its reference model (``REFERENCE``); the model is
the class ``Model`` in ``reference/<name>.py``, with
``logits(params, x)``, built from the configuration's sizes and a
compute dtype: float32 for the reference, bfloat16 for the
lower-precision control.  Parameters use the same dict layout as the
program's models, so one set of weights drawn by the benchmark feeds
both.  The loss and the FedCore features below are shared.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp


def build(kind: str, dtype=jnp.float32, **sizes):
    return importlib.import_module(f"reference.{kind}").Model(dtype=dtype,
                                                               **sizes)


def nll_of(logits, y):
    """Per-sample negative log-likelihood; a sequence's is the mean over
    its positions."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return nll.reshape(nll.shape[0], -1).mean(axis=1)


def nll(model, params, x, y):
    """Per-sample negative log-likelihood of the model's logits."""
    return nll_of(model.logits(params, x), y)


def loss(model, params, x, y, w):
    """Σ w·nll / Σ w over a batch (zero-weight rows contribute nothing)."""
    return jnp.sum(nll(model, params, x, y) * w) / jnp.maximum(jnp.sum(w),
                                                               1e-9)


def features(model, params, x, y):
    """FedCore's last-layer gradient proxy per sample:
    (softmax(z) − onehot(y)) W_outᵀ, averaged over a sequence's
    positions."""
    z = model.logits(params, x).astype(jnp.float32)
    p = jax.nn.softmax(z, axis=-1)
    r = p - jax.nn.one_hot(y, z.shape[-1], dtype=jnp.float32)
    g = r.astype(model.dtype) @ params["w_out"].astype(model.dtype).T
    g = g.astype(jnp.float32)
    return g.reshape(g.shape[0], -1, g.shape[-1]).mean(axis=1)
