"""LEAF / FedAvg stacked character LSTM on the Shakespeare population.

Source: McMahan et al., arXiv 1602.05629 §3, and LEAF
``models/shakespeare/stacked_lstm.py``: an 8-dimensional character
embedding, two LSTM layers of 256 units, sequences of 80 characters, an
80-way softmax at every position.  The population is FedProx's
Shakespeare (arXiv 1812.06127): 143 devices, mean 3,616 and std 6,808
samples per device.

Run as the program's ``CharLSTM(vocab=80, d_embed=8, d_hidden=256,
n_layers=2)``.
"""
from __future__ import annotations

import numpy as np

NAME = "shakespeare_lstm"
SOURCE = "https://arxiv.org/abs/1602.05629"
# per-device samples are capped at 2048 (the largest selection shape
# measured on one v5e), so that one round's selection fits a window
REDUCED = ["max_samples"]
ASSUMED = {
    "charset": "ids are drawn from a 64-symbol charset inside the 80-way "
               "softmax",
    "text": "per-device order-1 Markov chains: a shared sparse bigram "
            "matrix with a per-device style perturbation, from the seed",
    "sizes": "lognormal with FedProx's mean and std, at least 32 samples",
}
MODEL = {"vocab": 80, "d_embed": 8, "d_hidden": 256, "n_layers": 2,
         "seq_len": 80, "charset": 64}
POPULATION = {"n_clients": 143, "mean_samples": 3616.0,
              "std_samples": 6808.0, "min_samples": 32,
              "max_samples": 2048}
FLEET = {"epochs": 2, "batch_size": 32, "lr": 0.05, "max_sweeps": 1000}
REFERENCE = "lstm"
PRECISION = "default"
TINY = {"model": {"vocab": 12, "d_embed": 4, "d_hidden": 8, "n_layers": 2,
                  "seq_len": 6, "charset": 10},
        "population": {"n_clients": 20, "mean_samples": 60.0,
                       "std_samples": 40.0, "min_samples": 8,
                       "max_samples": 128}}


def workload(model=MODEL):
    from repro.fed.fleet.workloads import ArraySpec, FleetWorkload
    from repro.models.small import CharLSTM

    s = model["seq_len"]
    return FleetWorkload(
        name=NAME,
        model=CharLSTM(vocab=model["vocab"], d_embed=model["d_embed"],
                       d_hidden=model["d_hidden"],
                       n_layers=model["n_layers"]),
        schema={"x": ArraySpec((s,), "int32"), "y": ArraySpec((s,), "int32")},
        make_clients=_benchmark_draws_data,
        description="LEAF/FedAvg stacked character LSTM")


def _benchmark_draws_data(*_, **__):
    raise NotImplementedError("the benchmark draws client data itself")


def reference_sizes(model=MODEL) -> dict:
    return {k: model[k] for k in ("vocab", "d_embed", "d_hidden",
                                  "n_layers")}


def init_params(key, model=MODEL):
    """Weights in the program's layout: embedding N(0, 0.1²), dense and
    recurrent matrices N(0, 1/fan_in), biases zero."""
    import jax
    import jax.numpy as jnp

    v, e, h = model["vocab"], model["d_embed"], model["d_hidden"]
    ks = jax.random.split(key, 2 + 2 * model["n_layers"])

    def dense(k, n_in, n_out):
        return jax.random.normal(k, (n_in, n_out), jnp.float32) / np.sqrt(n_in)

    params = {"embed": 0.1 * jax.random.normal(ks[0], (v, e), jnp.float32),
              "w_out": dense(ks[1], h, v),
              "b_out": jnp.zeros((v,), jnp.float32)}
    d_in = e
    for i in range(model["n_layers"]):
        params[f"lstm{i}"] = {"wx": dense(ks[2 + 2 * i], d_in, 4 * h),
                              "wh": dense(ks[3 + 2 * i], h, 4 * h),
                              "b": jnp.zeros((4 * h,), jnp.float32)}
        d_in = h
    return params


def make_data(key, sizes, model=MODEL):
    """{"x", "y": (N, S) int32}: each client's windows of its own chain,
    y the next character of x."""
    import jax
    import jax.numpy as jnp

    q, s = model["charset"], model["seq_len"]
    n_clients = len(sizes)
    length = int(np.max(sizes)) + s + 1
    k1, k2, k3, k4 = jax.random.split(key, 4)
    marg = 1.0 / jnp.arange(1, q + 1) ** 1.1
    base = jax.random.gamma(k1, 0.3, (q, q)) * marg[None, :]
    style = jax.random.gamma(k2, 2.5, (n_clients, q, q)) * 0.4
    logp = jnp.log(base[None] * style + 1e-12)            # (C, q, q)
    first = jax.random.randint(k3, (n_clients,), 0, q)

    def step(prev, k):
        nxt = jax.random.categorical(
            k, jnp.take_along_axis(logp, prev[:, None, None], axis=1)[:, 0])
        return nxt, nxt

    _, chain = jax.lax.scan(step, first, jax.random.split(k4, length - 1))
    chain = jnp.concatenate([first[None], chain]).T        # (C, length)
    owner = np.repeat(np.arange(n_clients), sizes)
    start = np.concatenate([np.arange(m) for m in sizes])
    win = start[:, None] + np.arange(s)[None]
    who = jnp.asarray(owner)[:, None]
    x = chain[who, jnp.asarray(win)]
    y = chain[who, jnp.asarray(win + 1)]
    return {"x": x.astype(jnp.int32), "y": y.astype(jnp.int32)}


def model_flops(model=MODEL) -> dict:
    """Multiply-adds ×2 of one sequence.  ``train``: forward and backward
    (weight and input gradients of every layer; the embedding lookup
    counts nothing).  ``features``: forward plus (softmax − onehot)
    W_outᵀ at every position."""
    v, e, h, s = (model["vocab"], model["d_embed"], model["d_hidden"],
                  model["seq_len"])
    per_step = 0
    d_in = e
    for _ in range(model["n_layers"]):
        per_step += 2 * (d_in + h) * 4 * h
        d_in = h
    head = 2 * h * v
    fwd = s * (per_step + head)
    return {"train": 3 * fwd, "features": fwd + s * head}
