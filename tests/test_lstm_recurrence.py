"""CharLSTM's recurrence with its hand-written VJP against autodiff.

The oracle is the per-step layer the model used before: ``x_t @ wx`` inside
the ``lax.scan`` body, differentiated by JAX.  At ``highest`` matmul
precision the two agree to float32 rounding — only the order of the
weight gradients' sums differs — in the loss, every parameter gradient,
the engine's lane-vmapped SGD scan, and the ``grad_features`` forward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed.fleet.batched import FleetConfig, FleetEngine
from repro.models.small import IGNORE, CharLSTM

PRESETS = {
    # the charlm fleet workload's model and sequence length
    "charlm": (dict(vocab=32, d_embed=8, d_hidden=32, n_layers=1), 16),
    "two_layer": (dict(vocab=24, d_embed=6, d_hidden=16, n_layers=2), 12),
}


class PerStepLSTM(CharLSTM):
    """The autodiff oracle: the input projection inside the scan body."""

    def _lstm_layer(self, p, x):
        b = x.shape[0]
        h0 = jnp.zeros((b, self.d_hidden))

        def step(carry, x_t):
            h, c = carry
            gates = x_t @ p["wx"] + h @ p["wh"] + p["b"]
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(step, (h0, h0), x.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2)

    def hidden(self, params, tokens):
        x = params["embed"][tokens]
        for i in range(self.n_layers):
            x = self._lstm_layer(params[f"lstm{i}"], x)
        return x


def _params(model, seed):
    p = model.init(jax.random.PRNGKey(seed))
    # nonzero biases, so every gate's term shows in the gradients
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                              a.shape), p)


def _batch(rng, vocab, n, s):
    """Tokens with IGNORE'd target tails and zero-weight (padded) rows."""
    y = rng.integers(0, vocab, (n, s))
    y[0, s // 2:] = IGNORE
    y[-1, :] = IGNORE
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w[1] = w[-2] = 0.0
    return {"x": jnp.asarray(rng.integers(0, vocab, (n, s)), jnp.int32),
            "y": jnp.asarray(y, jnp.int32), "weights": jnp.asarray(w)}


def _close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize("case", ["loss_grad", "vmap_sgd", "grad_features"])
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_recurrence_matches_autodiff(preset, case):
    dims, s = PRESETS[preset]
    model, oracle = CharLSTM(**dims), PerStepLSTM(**dims)
    rng = np.random.default_rng(7)
    with jax.default_matmul_precision("highest"):
        if case == "loss_grad":
            batch = _batch(rng, dims["vocab"], 6, s)
            params = _params(model, 0)
            got = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
            want = jax.value_and_grad(oracle.loss, has_aux=True)(params,
                                                                 batch)
            _close(got, want)
        elif case == "vmap_sgd":
            # two client lanes with their own parameters and data, three
            # minibatch steps each, through the engine's per-client scan
            # vmapped as the group programs vmap it
            c, m, b, t = 2, 8, 4, 3
            params = jax.tree.map(lambda *a: jnp.stack(a),
                                  _params(model, 0), _params(model, 2))
            lanes = [_batch(rng, dims["vocab"], m, s) for _ in range(c)]
            data = {k: jnp.stack([d[k] for d in lanes]) for k in ("x", "y")}
            w = jnp.stack([d["weights"] for d in lanes])
            idx = jnp.asarray(rng.integers(0, m, (c, t, b)), jnp.int32)
            cfg = FleetConfig(lr=0.5)
            got = jax.vmap(FleetEngine(model, cfg)._sgd_scan)(
                params, data, w, idx)
            want = jax.vmap(FleetEngine(oracle, cfg)._sgd_scan)(
                params, data, w, idx)
            _close(got, want)
        else:
            batch = _batch(rng, dims["vocab"], 6, s)
            params = _params(model, 4)
            _close(model.grad_features(params, batch),
                   oracle.grad_features(params, batch))
            _close(model.logits(params, batch["x"]),
                   oracle.logits(params, batch["x"]))
