"""Pallas TPU kernel for the Scanner's hot loop (paper §4.1).

The paper measures "computing the predictions of the strong rules" /
accumulating candidate edges as the dominant compute cost. On CPU
Sparrow does a scalar scatter per example; a mechanical port of that
scatter would be hostile to the TPU (no efficient scatter in VMEM).

TPU adaptation (DESIGN.md §3): recast the histogram scatter as a
*one-hot matmul* so the MXU does the accumulation —

    hist[j, b]  =  sum_i wy_i * [xb[i, j] == b]
               =  (wy^T @ P)[j, b],   P[i, (j,b)] = [xb[i,j] == b]

Each grid step loads one (tile_n, d) block of binned features into
VMEM and builds P directly on a (tile_n, d*B) lane axis: one 0/1
matmul copies feature j onto lanes j*B .. j*B+B-1, and a compare with
each lane's bin index gives the one-hot (Mosaic cannot reshape a
(tile_n, d, B) block onto that axis). P is contracted against the
weight column on the MXU at full f32 precision, accumulating into a
resident (1, d*B) output row that the wrapper reshapes to (d, B).
The stopping-rule scalars (W = sum|w|, V = sum w^2, T = sum wy)
ride along in the same pass, so one sweep over the tile produces
everything the stopping rule needs — the paper's "one scan" structure,
VMEM-tiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


#: cap on one grid step's lane-axis one-hot, in elements: two (tile_n,
#: d*B) f32 temporaries of this size stay inside the 16 MiB scoped VMEM
_ONEHOT_ELEMS = 1 << 20


def _edge_scan_kernel(xb_ref, wy_ref, w_ref, expand_ref, lane_bin_ref, hist_ref, scal_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)
        scal_ref[...] = jnp.zeros_like(scal_ref)

    wy = wy_ref[...]  # (tn, 1) f32 (zero on padded rows)
    w = w_ref[...]  # (tn, 1) f32
    # lane-expand the bins: xe[i, j*B + b] = xb[i, j]. A 0/1 matmul on
    # small integers, exact in bf16 with f32 accumulation.
    xb = xb_ref[...].astype(jnp.float32).astype(jnp.bfloat16)  # (tn, d)
    xe = jnp.dot(xb, expand_ref[...], preferred_element_type=jnp.float32)  # (tn, d*B)
    p = (xe == lane_bin_ref[...]).astype(jnp.float32)  # one-hot on the lane axis
    # wy^T @ P on the MXU; full f32 contraction (the TPU default may
    # round the f32 operand to bf16)
    hist_ref[...] += jax.lax.dot_general(
        wy,
        p,
        (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (1, d*B)

    lane = jax.lax.broadcasted_iota(jnp.int32, scal_ref.shape, 1)
    sum_abs = jnp.sum(jnp.abs(w), axis=0, keepdims=True)
    sum_sq = jnp.sum(w * w, axis=0, keepdims=True)
    sum_wy = jnp.sum(wy, axis=0, keepdims=True)
    scal_ref[...] += jnp.where(
        lane == 0, sum_abs, jnp.where(lane == 1, sum_sq, jnp.where(lane == 2, sum_wy, 0.0))
    )


def lane_expansion(d: int, width: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(E, lane)`` for a lane-axis one-hot of ``d`` features x ``width``
    values: ``E`` (d, d*width) bf16 copies feature j onto lanes
    ``j*width .. j*width+width-1``, and ``lane`` (1, d*width) f32 holds
    each lane's value index."""
    expand = np.repeat(np.eye(d, dtype=np.float32), width, axis=1)
    lane = np.tile(np.arange(width, dtype=np.float32), d)[None, :]
    return jnp.asarray(expand, jnp.bfloat16), jnp.asarray(lane)


def onehot_tile(tile_n: int, width: int) -> int:
    """``tile_n``, shrunk to a multiple of 8 where its (tile, width)
    one-hot would exceed the per-step budget."""
    return max(8, min(tile_n, _ONEHOT_ELEMS // width // 8 * 8))


@functools.partial(
    jax.jit, static_argnames=("num_bins", "tile_n", "interpret")
)
def edge_scan(
    xb: jnp.ndarray,
    wy: jnp.ndarray,
    w: jnp.ndarray,
    *,
    num_bins: int,
    tile_n: int = 512,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Accumulate the (feature, bin) wy-histogram + stopping-rule scalars.

    Args:
        xb: (n, d) int32 binned features.
        wy: (n,) f32 signed weights ``w_i * y_i``.
        w:  (n,) f32 weights.
        num_bins: B (static).
        tile_n: rows per grid step (VMEM tile height); capped so the
            (tile_n, d*B) one-hot fits VMEM.
        interpret: ``None`` follows the platform (compiled on a TPU,
            interpreted elsewhere).

    Returns:
        (hist (d, B) f32, W (), V (), T ()).
    """
    n, d = xb.shape
    tile_n = onehot_tile(tile_n, d * num_bins)
    n_pad = -n % tile_n
    if n_pad:
        xb = jnp.pad(xb, ((0, n_pad), (0, 0)))
        wy = jnp.pad(wy, (0, n_pad))
        w = jnp.pad(w, (0, n_pad))
    steps = xb.shape[0] // tile_n
    wy2 = wy.reshape(-1, 1)
    w2 = w.reshape(-1, 1)
    expand, lane_bin = lane_expansion(d, num_bins)
    width = d * num_bins

    hist, scal = pl.pallas_call(
        _edge_scan_kernel,
        name="edge_scan",
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((d, width), lambda i: (0, 0)),
            pl.BlockSpec((1, width), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, width), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, width), jnp.float32),
            jax.ShapeDtypeStruct((1, 4), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xb, wy2, w2, expand, lane_bin)
    return hist.reshape(d, num_bins), scal[0, 0], scal[0, 1], scal[0, 2]
