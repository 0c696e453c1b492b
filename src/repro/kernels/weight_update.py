"""Pallas TPU kernel for the fused incremental weight update
(paper §4.1 "Incremental Updates" / Algorithm 2 ``UPDATEWEIGHT``).

The strong-rule margin delta is recast as the same one-hot contraction
used by ``edge_scan``: scatter the model's stump slice into candidate
space *once* on the host (O(T) work),

    A[j, t]  =  sum_{k in slice: feat_k = j, thr_k = t}  alpha_k * sign_k
    c        =  sum_{k in slice}  alpha_k * sign_k

then per example the margin delta is

    H_hi(x) - H_lo(x)  =  2 * (P[i, :] @ A) - c,
    P[i, (j, t)]       =  [xb[i, j] > t]

one masked lane sum of P against A (flattened to a (1, d*(B-1)) row)
per VMEM tile, with P built on the lane axis as in ``edge_scan``,
followed by the elementwise weight epilogue on the VPU:

    margin' = margin_l + delta
    w       = exp(-y * (margin' - margin_s))        (clipped)

This removes the HBM round-trip between "compute predictions" and
"compute weights" that dominates Sparrow's CPU profile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.boosting.stumps import StumpModel
from repro.kernels.edge_scan import lane_expansion, onehot_tile
from repro.kernels.platform import resolve_interpret

_CLIP = 30.0


def _weight_update_kernel(
    xb_ref, y_ref, ml_ref, ms_ref, expand_ref, lane_cut_ref, a_ref, c_ref, mout_ref, wout_ref
):
    # lane-expand the bins onto (tn, d*(B-1)) exactly as edge_scan does
    xb = xb_ref[...].astype(jnp.float32).astype(jnp.bfloat16)  # (tn, d)
    xe = jnp.dot(xb, expand_ref[...], preferred_element_type=jnp.float32)
    p = xe > lane_cut_ref[...]  # P[i, (j, t)] = [xb[i, j] > t]
    # P @ A as a masked lane sum on the VPU: exact f32, no N=1 matmul
    pa = jnp.sum(jnp.where(p, a_ref[...], 0.0), axis=1, keepdims=True)  # (tn, 1)
    delta = 2.0 * pa - c_ref[...]
    m_new = ml_ref[...] + delta  # (tn, 1)
    logw = -y_ref[...] * (m_new - ms_ref[...])
    mout_ref[...] = m_new
    wout_ref[...] = jnp.exp(jnp.clip(logw, -_CLIP, _CLIP))


@functools.partial(jax.jit, static_argnames=("num_bins", "tile_n", "interpret"))
def weight_update(
    xb: jnp.ndarray,
    y: jnp.ndarray,
    margin_l: jnp.ndarray,
    margin_s: jnp.ndarray,
    a: jnp.ndarray,
    c: jnp.ndarray,
    *,
    num_bins: int,
    tile_n: int = 512,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused incremental margin + weight refresh over a block of examples.

    Args:
        xb: (n, d) int32 bins.
        y: (n,) labels +-1.
        margin_l: (n,) margins at each example's last refresh.
        margin_s: (n,) margins at sampling time.
        a: (d, B-1) scattered stump-slice coefficients (see module doc).
        c: () scalar sum of the slice's alpha*sign.
        num_bins: B (static).
        interpret: ``None`` follows the platform (compiled on a TPU,
            interpreted elsewhere).

    Returns:
        (margin_new (n,), w (n,)) with ``w = exp(-y (margin_new - margin_s))``.
    """
    n, d = xb.shape
    num_cuts = num_bins - 1
    width = d * num_cuts
    tile_n = onehot_tile(tile_n, width)
    n_pad = -n % tile_n
    if n_pad:
        xb = jnp.pad(xb, ((0, n_pad), (0, 0)))
        y = jnp.pad(y, (0, n_pad), constant_values=1.0)
        margin_l = jnp.pad(margin_l, (0, n_pad))
        margin_s = jnp.pad(margin_s, (0, n_pad))
    steps = xb.shape[0] // tile_n
    col = lambda v: v.reshape(-1, 1)
    expand, lane_cut = lane_expansion(d, num_cuts)
    row_spec = pl.BlockSpec((tile_n, 1), lambda i: (i, 0))

    m_new, w = pl.pallas_call(
        _weight_update_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),
            row_spec,
            row_spec,
            row_spec,
            pl.BlockSpec((d, width), lambda i: (0, 0)),
            pl.BlockSpec((1, width), lambda i: (0, 0)),
            pl.BlockSpec((1, width), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((xb.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((xb.shape[0], 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(
        xb,
        col(y),
        col(margin_l),
        col(margin_s),
        expand,
        lane_cut,
        a.astype(jnp.float32).reshape(1, width),
        jnp.asarray(c, jnp.float32).reshape(1, 1),
    )
    return m_new[:n, 0], w[:n, 0]


def scatter_model_slice(
    model: StumpModel, t_lo: jnp.ndarray | int, t_hi: jnp.ndarray | int, num_bins: int, d: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Host-side O(T) prep: scatter stump slots [t_lo, t_hi) into the
    (d, B-1) candidate grid, returning (A, c) for :func:`weight_update`."""
    slot = jnp.arange(model.capacity)
    live = ((slot >= t_lo) & (slot < t_hi)).astype(jnp.float32)
    coef = model.alpha * model.sign * live
    a = jnp.zeros((d, num_bins - 1), jnp.float32).at[model.feat, model.thr].add(coef)
    return a, jnp.sum(coef)
