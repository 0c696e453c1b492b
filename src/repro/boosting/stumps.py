"""Decision stumps over pre-binned features, and the strong rule.

A weak rule is ``h_{j,t,s}(x) = s * (2*[bin(x_j) > t] - 1)`` for feature
``j``, bin-threshold ``t`` and sign ``s``. The strong rule is
``H(x) = sum_k alpha_k * h_k(x)`` stored as fixed-capacity arrays so the
whole model is a jit-friendly pytree (the TMSN broadcast payload).

Edges of *all* candidate stumps are computed from a single
``(features x bins)`` weighted histogram — the same trick XGBoost /
LightGBM use — so one pass over a chunk of examples updates every
candidate at once. The Pallas kernel ``repro.kernels.edge_scan``
implements the histogram accumulation for the TPU target; this module
is the pure-jnp path used on CPU and as the kernel oracle.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class StumpModel(NamedTuple):
    """Fixed-capacity strong rule (a pytree; broadcastable as-is)."""

    feat: jnp.ndarray  # (T,) int32 — feature index per stump
    thr: jnp.ndarray  # (T,) int32 — bin threshold per stump
    sign: jnp.ndarray  # (T,) float32 — +1/-1
    alpha: jnp.ndarray  # (T,) float32 — stump weight
    count: jnp.ndarray  # () int32 — number of live stumps

    @property
    def capacity(self) -> int:
        return self.feat.shape[0]


def empty_model(capacity: int) -> StumpModel:
    return StumpModel(
        feat=jnp.zeros((capacity,), jnp.int32),
        thr=jnp.zeros((capacity,), jnp.int32),
        sign=jnp.ones((capacity,), jnp.float32),
        alpha=jnp.zeros((capacity,), jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def append_stump(
    model: StumpModel, feat: jnp.ndarray, thr: jnp.ndarray, sign: jnp.ndarray, alpha: jnp.ndarray
) -> StumpModel:
    """Append one weak rule (functional; no-op if at capacity)."""
    k = jnp.minimum(model.count, model.capacity - 1)
    ok = model.count < model.capacity
    upd = lambda a, v: a.at[k].set(jnp.where(ok, v, a[k]))
    return StumpModel(
        feat=upd(model.feat, jnp.asarray(feat, jnp.int32)),
        thr=upd(model.thr, jnp.asarray(thr, jnp.int32)),
        sign=upd(model.sign, jnp.asarray(sign, jnp.float32)),
        alpha=upd(model.alpha, jnp.asarray(alpha, jnp.float32)),
        count=model.count + jnp.asarray(ok, jnp.int32),
    )


def alpha_from_gamma(gamma: jnp.ndarray | float) -> jnp.ndarray:
    """AdaBoost weak-rule weight for a certified edge:
    ``alpha = 1/2 log((1/2 + gamma) / (1/2 - gamma))`` (Algorithm 1)."""
    g = jnp.clip(jnp.asarray(gamma, jnp.float32), -0.49, 0.49)
    return 0.5 * jnp.log((0.5 + g) / (0.5 - g))


def _stump_preds(model: StumpModel, xb: jnp.ndarray) -> jnp.ndarray:
    """(n, T) predictions of every stored stump on binned rows ``xb``."""
    gathered = xb[:, model.feat]  # (n, T)
    return jnp.where(gathered > model.thr[None, :], 1.0, -1.0) * model.sign[None, :]


def predict_margin(model: StumpModel, xb: jnp.ndarray) -> jnp.ndarray:
    """Full strong-rule margin ``H(x)`` for binned rows ``xb`` (n, d)."""
    preds = _stump_preds(model, xb)  # (n, T)
    live = (jnp.arange(model.capacity) < model.count).astype(jnp.float32)
    return preds @ (model.alpha * live)


#: (rows, T) stump predictions materialized at once by
#: :func:`predict_margin_delta`; longer inputs run block by block (a
#: full disk pass at 7.2M rows x 256 stumps would need 7.4 GB)
_DELTA_BLOCK_ELEMS = 1 << 22


def _margin_delta(model: StumpModel, xb: jnp.ndarray, t_from: jnp.ndarray) -> jnp.ndarray:
    preds = _stump_preds(model, xb)  # (n, T)
    slot = jnp.arange(model.capacity)[None, :]
    live = (slot >= t_from[:, None]) & (slot < model.count)
    return jnp.sum(preds * model.alpha[None, :] * live.astype(jnp.float32), axis=1)


def predict_margin_delta(
    model: StumpModel, xb: jnp.ndarray, t_from: jnp.ndarray
) -> jnp.ndarray:
    """Incremental margin: ``H_t(x) - H_{t_from}(x)`` per example.

    ``t_from`` is per-example (n,) — the stump count at the example's
    last weight refresh (paper §4.1 "Incremental Updates": Scanner and
    Sampler share the burden of computing the weights). Rows are
    processed in blocks of ``_DELTA_BLOCK_ELEMS // T``; each row's value
    does not depend on the blocking.
    """
    n = xb.shape[0]
    rows = max(8, _DELTA_BLOCK_ELEMS // model.capacity)
    if n <= rows:
        return _margin_delta(model, xb, t_from)
    full, rem = divmod(n, rows)

    def block(i, out):
        start = i * rows
        part = _margin_delta(
            model,
            jax.lax.dynamic_slice_in_dim(xb, start, rows),
            jax.lax.dynamic_slice_in_dim(t_from, start, rows),
        )
        return jax.lax.dynamic_update_slice_in_dim(out, part, start, 0)

    out = jax.lax.fori_loop(0, full, block, jnp.zeros((n,), jnp.float32))
    if rem:
        out = out.at[n - rem :].set(_margin_delta(model, xb[n - rem :], t_from[n - rem :]))
    return out


def exp_loss(model: StumpModel, xb: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Average exponential-loss potential ``Z_S(H)`` (paper §3)."""
    return jnp.mean(jnp.exp(-y * predict_margin(model, xb)))


def error_rate(model: StumpModel, xb: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    margin = predict_margin(model, xb)
    pred = jnp.where(margin >= 0, 1.0, -1.0)
    return jnp.mean(pred != y)


def model_payload_bytes(model: StumpModel) -> int:
    """Broadcast payload size of a strong rule (for comm accounting)."""
    return sum(int(x.size * x.dtype.itemsize) for x in model)


# --------------------------------------------------------------------------
# Candidate-edge machinery: one (d, B) weighted histogram covers every
# candidate stump.
# --------------------------------------------------------------------------


def edge_histogram(xb: jnp.ndarray, wy: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Scatter-add ``wy`` into per-(feature, bin) cells.

    Args:
        xb: (n, d) int bins.
        wy: (n,) signed weights ``w_i * y_i``.
        num_bins: B.

    Returns:
        (d, B) float32 histogram; ``hist[j, b] = sum_{i: xb[i,j]=b} wy_i``.
    """
    n, d = xb.shape
    hist = jnp.zeros((d, num_bins), jnp.float32)
    cols = jnp.broadcast_to(jnp.arange(d)[None, :], (n, d))
    return hist.at[cols, xb].add(wy[:, None])


def edges_from_histogram(hist: jnp.ndarray) -> jnp.ndarray:
    """Per-candidate signed edge mass from a wy-histogram.

    ``m[j, t] = sum_i wy_i h_{j,t}(x_i) = 2 * G_j(t) - T`` where
    ``G_j(t) = sum_{b > t} hist[j, b]`` and ``T = sum_i wy_i``.

    Returns (d, B-1): thresholds t in [0, B-2].
    """
    total = jnp.sum(hist, axis=1, keepdims=True)  # = sum_i wy_i, per feature row
    # suffix sums over bins strictly greater than t
    rev_cum = jnp.cumsum(hist[:, ::-1], axis=1)[:, ::-1]  # G_j(t-1) = sum_{b>=t}
    g = rev_cum[:, 1:]  # G_j(t) for t = 0..B-2
    return 2.0 * g - total


def best_stump_exact(
    xb: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, num_bins: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact greedy best stump over the full weighted set.

    Returns (feat, thr, sign, gamma_hat) where ``gamma_hat`` is the
    empirical (normalized) edge of the chosen stump.
    """
    wy = w * y
    hist = edge_histogram(xb, wy, num_bins)
    m = edges_from_histogram(hist)  # (d, B-1)
    W = jnp.sum(jnp.abs(w))
    flat = jnp.abs(m).ravel()
    idx = jnp.argmax(flat)
    feat = idx // m.shape[1]
    thr = idx % m.shape[1]
    raw = m[feat, thr]
    sign = jnp.where(raw >= 0, 1.0, -1.0)
    gamma_hat = jnp.abs(raw) / jnp.maximum(W, 1e-30) / 2.0
    return feat.astype(jnp.int32), thr.astype(jnp.int32), sign, gamma_hat


def bin_features(x: jnp.ndarray, num_bins: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantile-bin raw float features into int32 bins.

    Returns (bins (n,d) int32, cut_points (d, B-1)). This is the usual
    GBDT pre-processing step (XGBoost approximate greedy / LightGBM
    histograms); Sparrow's stumps operate on the same binned view.
    """
    qs = jnp.linspace(0.0, 1.0, num_bins + 1)[1:-1]
    cuts = jnp.quantile(x, qs, axis=0).T  # (d, B-1)
    bins = jnp.sum(x[:, :, None] > cuts[None, :, :], axis=2).astype(jnp.int32)
    return bins, cuts
