"""The paper's own evaluation models (§6.1): logistic regression for the
Synthetic(α,β) benchmark, a small CNN for (pseudo-)MNIST, and an LSTM
char-LM for the Shakespeare-style benchmark.

Each exposes the FLModel interface used by the federated runtime:
  init(key) -> params
  loss(params, batch) -> (scalar, metrics)        [supports batch["weights"]]
  accuracy(params, batch) -> scalar
  grad_features(params, batch) -> (B, F)          [FedCore §4.3 proxies]
  feature_space: "input" (convex d̃) or "last_layer_grad" (DNN d̂)
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

IGNORE = -100


def _weighted_ce(logits, labels, weights=None):
    """logits (B, ..., C); labels (B, ...); weights (B,) or None."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels != IGNORE
    safe = jnp.where(valid, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = nll * valid
    axes = tuple(range(1, nll.ndim))
    per_example = (jnp.sum(nll, axis=axes)
                   / jnp.maximum(jnp.sum(valid, axis=axes), 1))
    if weights is None:
        weights = jnp.ones(per_example.shape[0], jnp.float32)
    total = jnp.sum(per_example * weights) / jnp.maximum(jnp.sum(weights),
                                                         1e-9)
    return total, per_example


def _last_layer_grad_feature(logits, labels, w_out):
    """FedCore §4.3 DNN proxy: dL/dz = (softmax(logits) - onehot(y)) W_outᵀ.

    logits (B, ..., C); w_out (F, C).  Token/position axes are mean-pooled
    so each *sample* yields one feature vector (the per-sample gradient the
    k-medoids clustering runs on).
    """
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    valid = (labels != IGNORE)
    safe = jnp.where(valid, labels, 0)
    onehot = jax.nn.one_hot(safe, logits.shape[-1], dtype=jnp.float32)
    dlogits = (p - onehot) * valid[..., None]
    feat = dlogits @ w_out.T.astype(jnp.float32)  # (B, ..., F)
    if feat.ndim > 2:
        axes = tuple(range(1, feat.ndim - 1))
        feat = (jnp.sum(feat, axis=axes)
                / jnp.maximum(jnp.sum(valid, axis=axes), 1)[..., None])
    return feat


# ---------------------------------------------------------------------------
# Logistic regression (Synthetic benchmark; convex -> input-space distances)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LogisticRegression:
    n_features: int = 60
    n_classes: int = 10
    feature_space: str = "input"

    def init(self, key):
        return {"w": jnp.zeros((self.n_features, self.n_classes)),
                "b": jnp.zeros((self.n_classes,))}

    def logits(self, params, x):
        return x @ params["w"] + params["b"]

    def loss(self, params, batch):
        logits = self.logits(params, batch["x"])
        total, per_example = _weighted_ce(logits, batch["y"],
                                          batch.get("weights"))
        return total, {"loss": total, "per_example_loss": per_example}

    def accuracy(self, params, batch):
        logits = self.logits(params, batch["x"])
        return jnp.mean(jnp.argmax(logits, -1) == batch["y"])

    def grad_features(self, params, batch):
        # convex model: paper uses input-space Euclidean distances (d̃)
        return batch["x"]


# ---------------------------------------------------------------------------
# Small CNN (MNIST benchmark)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SmallCNN:
    """Three-layer CNN: 2 conv (5x5) + 1 dense head, as in the paper."""
    image_size: int = 28
    channels: Tuple[int, int] = (16, 32)
    n_classes: int = 10
    feature_space: str = "last_layer_grad"

    def init(self, key):
        ks = jax.random.split(key, 4)
        c1, c2 = self.channels
        s = self.image_size // 4  # two 2x2 pools
        return {
            "conv1": jax.random.normal(ks[0], (5, 5, 1, c1)) * 0.1,
            "b1": jnp.zeros((c1,)),
            "conv2": jax.random.normal(ks[1], (5, 5, c1, c2)) * 0.1,
            "b2": jnp.zeros((c2,)),
            "w_out": dense_init(ks[2], s * s * c2, self.n_classes),
            "b_out": jnp.zeros((self.n_classes,)),
        }

    def _features(self, params, x):
        """x: (B, H, W) or (B, H, W, 1) -> (B, F) pre-head features."""
        if x.ndim == 3:
            x = x[..., None]
        for w, b in ((params["conv1"], params["b1"]),
                     (params["conv2"], params["b2"])):
            x = jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
            x = jax.nn.relu(x)
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        return x.reshape(x.shape[0], -1)

    def logits(self, params, x):
        return self._features(params, x) @ params["w_out"] + params["b_out"]

    def loss(self, params, batch):
        logits = self.logits(params, batch["x"])
        total, per_example = _weighted_ce(logits, batch["y"],
                                          batch.get("weights"))
        return total, {"loss": total, "per_example_loss": per_example}

    def accuracy(self, params, batch):
        logits = self.logits(params, batch["x"])
        return jnp.mean(jnp.argmax(logits, -1) == batch["y"])

    def grad_features(self, params, batch):
        logits = self.logits(params, batch["x"])
        return _last_layer_grad_feature(logits, batch["y"], params["w_out"])


# ---------------------------------------------------------------------------
# LSTM char-LM (Shakespeare benchmark)
# ---------------------------------------------------------------------------

def _lstm_scan(wh, b, xw):
    """The recurrence over time-major input projections.

    xw (S, B, 4H) holds ``x_t @ wx`` for every step; gates (i, f, g, o)
    with the forget gate's +1.  Returns hs (S, B, H) and the per-step
    activations the backward reads: the four gates, c and tanh(c).
    """
    h0 = jnp.zeros((xw.shape[1], wh.shape[0]), xw.dtype)

    def step(carry, xw_t):
        h, c = carry
        zi, zf, zg, zo = jnp.split(xw_t + h @ wh + b, 4, axis=-1)
        i, f = jax.nn.sigmoid(zi), jax.nn.sigmoid(zf + 1.0)
        g, o = jnp.tanh(zg), jax.nn.sigmoid(zo)
        c = f * c + i * g
        tc = jnp.tanh(c)
        h = o * tc
        return (h, c), (h, (i, f, g, o, c, tc))

    _, (hs, acts) = jax.lax.scan(step, (h0, h0), xw)
    return hs, acts


@jax.custom_vjp
def lstm_recurrence(wh, b, xw):
    """LSTM layer's recurrence: (wh (H, 4H), b (4H,), xw (S, B, 4H)) ->
    hs (S, B, H).  The backward carries only (dh, dc) through time and
    forms the weight gradients after the loop, as contractions over all
    S·B rows at once, instead of accumulating them step by step."""
    return _lstm_scan(wh, b, xw)[0]


def _lstm_recurrence_fwd(wh, b, xw):
    hs, acts = _lstm_scan(wh, b, xw)
    return hs, (wh, hs, acts)


def _lstm_recurrence_bwd(res, dhs):
    wh, hs, (i, f, g, o, cs, tcs) = res
    zero = jnp.zeros_like(hs[:1])
    h_prev = jnp.concatenate([zero, hs[:-1]])
    c_prev = jnp.concatenate([zero, cs[:-1]])

    # the derivatives as JAX's own rules write them: logistic s(1 - s),
    # tanh (1 + t)(1 - t)
    def step(carry, xs):
        dh, dc = carry
        dh_t, i, f, g, o, cp, tc = xs
        dh = dh + dh_t
        dc = dc + dh * o * ((1 + tc) * (1 - tc))
        dz = jnp.concatenate([dc * g * (i * (1 - i)),
                              dc * cp * (f * (1 - f)),
                              dc * i * ((1 + g) * (1 - g)),
                              dh * tc * (o * (1 - o))], axis=-1)
        return (dz @ wh.T, dc * f), dz

    _, dz = jax.lax.scan(step, (zero[0], zero[0]),
                         (dhs, i, f, g, o, c_prev, tcs), reverse=True)
    dwh = jnp.einsum("sbh,sbg->hg", h_prev, dz)
    return dwh, jnp.sum(dz, axis=(0, 1)), dz


lstm_recurrence.defvjp(_lstm_recurrence_fwd, _lstm_recurrence_bwd)


@dataclasses.dataclass(frozen=True)
class CharLSTM:
    vocab: int = 80
    d_embed: int = 8
    d_hidden: int = 128
    n_layers: int = 2
    feature_space: str = "last_layer_grad"

    def init(self, key):
        ks = jax.random.split(key, 2 + self.n_layers)
        params = {
            "embed": jax.random.normal(ks[0], (self.vocab, self.d_embed))
            * 0.1,
            "w_out": dense_init(ks[1], self.d_hidden, self.vocab),
            "b_out": jnp.zeros((self.vocab,)),
        }
        d_in = self.d_embed
        for i in range(self.n_layers):
            k1, k2 = jax.random.split(ks[2 + i])
            params[f"lstm{i}"] = {
                "wx": dense_init(k1, d_in, 4 * self.d_hidden),
                "wh": dense_init(k2, self.d_hidden, 4 * self.d_hidden),
                "b": jnp.zeros((4 * self.d_hidden,)),
            }
            d_in = self.d_hidden
        return params

    def _lstm_layer(self, p, x):
        """x: (S, B, D) time-major -> (S, B, H)."""
        # every step's input projection in one matmul, off the serial loop
        return lstm_recurrence(p["wh"], p["b"], x @ p["wx"])

    def hidden(self, params, tokens):
        x = params["embed"][tokens.T]                      # (S, B, D)
        for i in range(self.n_layers):
            x = self._lstm_layer(params[f"lstm{i}"], x)
        return x.transpose(1, 0, 2)                        # (B, S, H)

    def logits(self, params, tokens):
        return self.hidden(params, tokens) @ params["w_out"] + params["b_out"]

    def loss(self, params, batch):
        logits = self.logits(params, batch["x"])
        total, per_example = _weighted_ce(logits, batch["y"],
                                          batch.get("weights"))
        return total, {"loss": total, "per_example_loss": per_example}

    def accuracy(self, params, batch):
        logits = self.logits(params, batch["x"])
        valid = batch["y"] != IGNORE
        correct = (jnp.argmax(logits, -1) == batch["y"]) & valid
        return jnp.sum(correct) / jnp.maximum(jnp.sum(valid), 1)

    def grad_features(self, params, batch):
        logits = self.logits(params, batch["x"])
        return _last_layer_grad_feature(logits, batch["y"], params["w_out"])
