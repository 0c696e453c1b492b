"""Public jit'd entry points for the Pallas kernels.

``interpret=None`` (the default everywhere) follows the platform: on a
TPU the call sites get the compiled Mosaic kernels, on any other
backend the Pallas interpreter (see :mod:`repro.kernels.platform`).
Tile sizes default to MXU-aligned values; the one-hot kernels shrink
``tile_n`` where a (tile_n, d*B) one-hot would not fit VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.edge_scan import edge_scan as _edge_scan
from repro.kernels.round_step import queue_ingest as _queue_ingest, round_step as _round_step
from repro.kernels.weight_update import scatter_model_slice, weight_update as _weight_update


def edge_scan(
    xb: jnp.ndarray,
    wy: jnp.ndarray,
    w: jnp.ndarray,
    *,
    num_bins: int,
    tile_n: int = 512,
    interpret: bool | None = None,
):
    """(hist (d,B), W, V, T) — see :mod:`repro.kernels.edge_scan`."""
    return _edge_scan(xb, wy, w, num_bins=num_bins, tile_n=tile_n, interpret=interpret)


def edge_scan_batched(
    xb: jnp.ndarray,
    wy: jnp.ndarray,
    w: jnp.ndarray,
    *,
    num_bins: int,
    tile_n: int = 512,
    interpret: bool | None = None,
):
    """Batched edge scan over a leading worker axis.

    Args are the stacked counterparts of :func:`edge_scan`: ``xb``
    (W, n, d), ``wy``/``w`` (W, n). ``vmap`` of a ``pallas_call``
    prepends a batch dimension to the kernel grid, so all W histogram
    accumulations run in one launch. This standalone entry point is the
    kernel-level counterpart of what the batched Sparrow scanner does
    implicitly (it vmaps ``scan_chunk``, which calls :func:`edge_scan`
    inside the vmapped region — the same batch-grid lowering);
    ``tests/test_kernels.py`` pins the two-path equivalence against W
    independent launches.

    Returns (hist (W, d, B), W_ (W,), V (W,), T (W,)).
    """
    fn = functools.partial(_edge_scan, num_bins=num_bins, tile_n=tile_n, interpret=interpret)
    return jax.vmap(fn)(xb, wy, w)


def edge_scan_sharded(
    xb: jnp.ndarray,
    wy: jnp.ndarray,
    w: jnp.ndarray,
    *,
    mesh,
    num_bins: int,
    tile_n: int = 512,
    interpret: bool | None = None,
):
    """:func:`edge_scan_batched` sharded over a ``workers`` mesh axis.

    The kernel-level counterpart of the sharded engine's scan path:
    ``shard_map`` partitions the leading worker axis over the mesh, and
    each device runs the vmapped ``pallas_call`` on only its local
    worker shard — per-worker histograms need no collective at all (the
    (d, B) accumulation is private to a worker), so the whole scan is
    embarrassingly parallel and the launch grid per device shrinks from
    W to W_local. ``tests/test_kernels.py`` pins the output against the
    unsharded batched path when multiple devices are visible.
    """
    from jax.sharding import PartitionSpec as P

    if xb.shape[0] % mesh.shape["workers"]:
        raise ValueError(
            f"worker axis {xb.shape[0]} must divide over {mesh.shape['workers']} devices"
        )
    fn = functools.partial(_edge_scan, num_bins=num_bins, tile_n=tile_n, interpret=interpret)
    sharded = jax.shard_map(
        lambda a, b, c: jax.vmap(fn)(a, b, c),
        mesh=mesh,
        in_specs=(P("workers"), P("workers"), P("workers")),
        out_specs=(P("workers"), P("workers"), P("workers"), P("workers")),
        check_vma=False,
    )
    return sharded(xb, wy, w)


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
):
    """(margin_new, w) — see :mod:`repro.kernels.weight_update`."""
    return _weight_update(
        xb, y, margin_l, margin_s, a, c, num_bins=num_bins, tile_n=tile_n, interpret=interpret
    )


def round_deliver(
    q_cert: jnp.ndarray,
    q_due: jnp.ndarray,
    q_src: jnp.ndarray,
    q_slot: jnp.ndarray,
    certs0: jnp.ndarray,
    alive: jnp.ndarray,
    credit: jnp.ndarray,
    speed_norm: jnp.ndarray,
    r: jnp.ndarray,
    *,
    eps: float,
    tile_w: int = 128,
    interpret: bool | None = None,
):
    """Fused sparse delivery + eps-gated accept + laggard credit.

    Same contract as :func:`repro.kernels.ref.round_step_ref` (bool
    ``alive`` in, bool ``take``/``active`` out); the int32 conversion
    the TPU kernel needs at its boundary happens here.
    """
    out = _round_step(
        q_cert,
        q_due,
        q_src,
        q_slot,
        certs0,
        alive.astype(jnp.int32),
        credit,
        speed_norm,
        r,
        eps=eps,
        tile_w=tile_w,
        interpret=interpret,
    )
    q_cert_new, best_cert, best_src, best_slot, take, n_arr, credit_new, active = out
    return (
        q_cert_new,
        best_cert,
        best_src,
        best_slot,
        take != 0,
        n_arr,
        credit_new,
        active != 0,
    )


def queue_ingest(
    q_cert: jnp.ndarray,
    q_due: jnp.ndarray,
    q_src: jnp.ndarray,
    q_slot: jnp.ndarray,
    c_cert: jnp.ndarray,
    c_due: jnp.ndarray,
    c_src: jnp.ndarray,
    c_slot: jnp.ndarray,
    *,
    tile_w: int = 128,
    interpret: bool | None = None,
):
    """Sparse-control candidate-list ingest into the pending queues.

    Same contract as :func:`repro.kernels.ref.queue_ingest_ref` (all
    operands numeric — no boolean boundary conversion needed); returns
    ``(q_cert', q_due', q_src', q_slot')``.
    """
    return _queue_ingest(
        q_cert,
        q_due,
        q_src,
        q_slot,
        c_cert,
        c_due,
        c_src,
        c_slot,
        tile_w=tile_w,
        interpret=interpret,
    )


__all__ = [
    "edge_scan",
    "queue_ingest",
    "round_deliver",
    "edge_scan_batched",
    "edge_scan_sharded",
    "weight_update",
    "scatter_model_slice",
]
