"""Reference model ``lstm``: the LEAF / FedAvg stacked character LSTM, in
plain jax.numpy."""
from __future__ import annotations

import jax
import jax.numpy as jnp


class Model:
    """Stacked character LSTM: embedding, ``n_layers`` LSTM layers with
    gates (i, f, g, o) and the forget gate's +1 bias, a linear head at
    every position."""

    def __init__(self, vocab: int, d_embed: int, d_hidden: int,
                 n_layers: int, dtype):
        self.vocab = vocab
        self.d_embed = d_embed
        self.d_hidden = d_hidden
        self.n_layers = n_layers
        self.dtype = dtype

    def _layer(self, p, x):
        wx = p["wx"].astype(self.dtype)
        wh = p["wh"].astype(self.dtype)
        b = p["b"].astype(self.dtype)
        n = x.shape[0]
        h = jnp.zeros((n, self.d_hidden), self.dtype)
        c = jnp.zeros((n, self.d_hidden), self.dtype)
        xw = jnp.einsum("nsd,dg->sng", x, wx)          # input part, all steps

        def step(carry, xw_t):
            h, c = carry
            z = xw_t + h @ wh + b
            i, f, g, o = (z[:, j * self.d_hidden:(j + 1) * self.d_hidden]
                          for j in range(4))
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(step, (h, c), xw)
        return jnp.swapaxes(hs, 0, 1)                 # (N, S, H)

    def hidden(self, params, tokens):
        x = params["embed"].astype(self.dtype)[tokens]
        for i in range(self.n_layers):
            x = self._layer(params[f"lstm{i}"], x)
        return x

    def logits(self, params, tokens):
        return (self.hidden(params, tokens) @ params["w_out"].astype(self.dtype)
                + params["b_out"].astype(self.dtype))
