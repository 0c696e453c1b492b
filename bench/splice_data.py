"""The splice-site stand-in data, generated on the device from the
configuration's seed.

A copy of the program's ``make_splice_like`` and ``train_test_split``
(``src/repro/data/splice.py``), kept here so the benchmark's inputs do
not move when the program does. Generation and the split run as one
jitted call, so a cold start compiles one program instead of dozens of
eager ops. Only the four arrays reach the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(
    jax.jit,
    static_argnames=("n", "d", "num_bins", "n_signal", "pos_fraction", "label_noise", "test_fraction"),
)
def make_split(
    key: jax.Array,
    *,
    n: int,
    d: int,
    num_bins: int,
    n_signal: int,
    pos_fraction: float,
    label_noise: float,
    test_fraction: float,
):
    """``(xtr, ytr, xte, yte)``: int32 bins ``(n, d)`` and +-1 float32
    labels from a sparse ground-truth stump ensemble with label noise,
    split by a random permutation."""
    k_data, k_split = jax.random.split(key)
    k_x, k_sig, k_thr, k_sgn, k_noise = jax.random.split(k_data, 5)
    xb = jax.random.randint(k_x, (n, d), 0, num_bins, dtype=jnp.int32)

    sig_feats = jax.random.choice(k_sig, d, shape=(n_signal,), replace=False)
    sig_thr = jax.random.randint(k_thr, (n_signal,), 0, num_bins - 1)
    sig_sgn = jnp.where(jax.random.bernoulli(k_sgn, 0.5, (n_signal,)), 1.0, -1.0)
    weights = jnp.linspace(2.0, 0.5, n_signal)  # few strong + a tail of weak motifs

    votes = jnp.where(xb[:, sig_feats] > sig_thr[None, :], 1.0, -1.0) * sig_sgn[None, :]
    score = votes @ weights
    bias = jnp.quantile(score, 1.0 - pos_fraction)  # hits the positive fraction
    y = jnp.where(score > bias, 1.0, -1.0)
    flip = jax.random.bernoulli(k_noise, label_noise, (n,))
    y = jnp.where(flip, -y, y).astype(jnp.float32)

    perm = jax.random.permutation(k_split, n)
    n_test = int(n * test_fraction)
    te, tr = perm[:n_test], perm[n_test:]
    return xb[tr], y[tr], xb[te], y[te]


def make_data(cfg: dict):
    """The configuration's data set, from its own ``data.seed``."""
    data = cfg["data"]
    return make_split(
        jax.random.PRNGKey(int(data["seed"])),
        n=int(data["n"]),
        d=int(data["d"]),
        num_bins=int(data["num_bins"]),
        n_signal=int(data["n_signal"]),
        pos_fraction=float(data["pos_fraction"]),
        label_noise=float(data["label_noise"]),
        test_fraction=float(data["test_fraction"]),
    )
