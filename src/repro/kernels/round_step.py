"""Pallas TPU kernel for the engine's fused sparse round step.

The sparse engine (``EngineConfig.inflight_capacity > 0``) keeps a
bounded per-destination :class:`~repro.core.engine.PendingQueue` of
(cert, src, due, ring-slot) entries instead of the dense ``(W, W, D)``
in-flight buffer. Its per-round delivery hot path is four elementwise/
reduction passes over the ``(W, C)`` queue plus the per-worker credit
update — all VPU work with no cross-row dependence, so this kernel
fuses them into ONE pass per row tile:

  1. delivery argmin: among entries due this round, the minimum by
     (cert, src) — the same lexicographic tie-break as the dense
     engine's ``argmin`` (lowest source id wins ties);
  2. eps-gated accept: ``best_cert < certs0 - eps`` (the protocol's
     ``accepts``), masked to alive destinations;
  3. arrival clearing: delivered entries drop their cert to +inf
     (dues are absolute, so a stale due can never re-match — this
     replaces the dense buffer's O(W²·D) shift);
  4. laggard-credit update: ``credit += speed_norm``; workers whose
     credit covers a segment spend it (``active``).

Grid: one step per ``tile_w`` destination rows; every block is
resident for exactly one step (no cross-step accumulation). Boolean
masks cross the kernel boundary as int32 (TPU-friendly); the wrapper
converts. ``kernels/ref.py::round_step_ref`` is the bit-identical
pure-jnp oracle (and the engine's ``round_step_impl="ref"`` path).

:func:`queue_ingest` is the sparse-CONTROL-plane companion
(``EngineConfig.control_plane="sparse"``): instead of scanning a dense
(W,) broadcast-score vector, it merges an explicit (W, m) candidate
block — the scattered payload of the (n_dev, k) control all_gather —
into the pending queues with the same worst-certificate-first
eviction order, via a loop-free rank-select (see the kernel body) that
bit-matches the jnp oracle's stable lexsort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

_I32_MAX = 2**31 - 1


def _round_step_kernel(
    q_cert_ref,
    q_due_ref,
    q_src_ref,
    q_slot_ref,
    certs0_ref,
    alive_ref,
    credit_ref,
    speed_ref,
    r_ref,
    q_cert_out_ref,
    best_cert_ref,
    best_src_ref,
    best_slot_ref,
    take_ref,
    n_arr_ref,
    credit_out_ref,
    active_ref,
    *,
    eps: float,
):
    qc = q_cert_ref[...]  # (tw, C) f32
    qd = q_due_ref[...]  # (tw, C) i32
    qs = q_src_ref[...]  # (tw, C) i32
    ql = q_slot_ref[...]  # (tw, C) i32
    certs0 = certs0_ref[...]  # (tw, 1) f32
    alive = alive_ref[...] != 0  # (tw, 1) bool
    credit = credit_ref[...]  # (tw, 1) f32
    speed = speed_ref[...]  # (tw, 1) f32
    r = r_ref[0, 0]  # () i32

    arr = (qd == r) & jnp.isfinite(qc)  # entries delivered this round
    arr_live = jnp.where(arr & alive, qc, jnp.inf)
    best_cert = jnp.min(arr_live, axis=1, keepdims=True)  # (tw, 1)
    finite = jnp.isfinite(best_cert)
    hit = (arr_live == best_cert) & finite
    best_src = jnp.min(jnp.where(hit, qs, _I32_MAX), axis=1, keepdims=True)
    sel = hit & (qs == best_src)
    best_slot = jnp.min(jnp.where(sel, ql, _I32_MAX), axis=1, keepdims=True)

    best_cert_ref[...] = best_cert
    best_src_ref[...] = jnp.where(finite, best_src, 0)
    best_slot_ref[...] = jnp.where(finite, best_slot, 0)
    take_ref[...] = (finite & (best_cert < certs0 - eps)).astype(jnp.int32)
    n_arr_ref[...] = jnp.sum(arr.astype(jnp.int32), axis=1, keepdims=True)
    # delivered entries (dead destinations included — they drain and
    # count as arrivals exactly like the dense buffer's shift-out)
    q_cert_out_ref[...] = jnp.where(arr, jnp.inf, qc)

    credit2 = credit + speed
    active = alive & (credit2 >= 1.0 - 1e-6)
    credit_out_ref[...] = jnp.where(active, credit2 - 1.0, credit2)
    active_ref[...] = active.astype(jnp.int32)


def _queue_ingest_kernel(
    q_cert_ref,
    q_due_ref,
    q_src_ref,
    q_slot_ref,
    c_cert_ref,
    c_due_ref,
    c_src_ref,
    c_slot_ref,
    o_cert_ref,
    o_due_ref,
    o_src_ref,
    o_slot_ref,
):
    cert = jnp.concatenate([q_cert_ref[...], c_cert_ref[...]], axis=1)  # (tw, n)
    due = jnp.concatenate([q_due_ref[...], c_due_ref[...]], axis=1)
    src = jnp.concatenate([q_src_ref[...], c_src_ref[...]], axis=1)
    slot = jnp.concatenate([q_slot_ref[...], c_slot_ref[...]], axis=1)
    n = cert.shape[1]
    cap = q_cert_ref.shape[1]

    # rank-select instead of an in-kernel sort: with the column position
    # as the final tie-break the lex key (cert, src, due, position) is a
    # TOTAL order, so "rank = number of strict predecessors" is a
    # permutation of 0..n-1 that bit-matches the stable
    # lexsort((due, src, cert)) of the jnp oracle. One (n, n) pairwise
    # comparison per row, all VPU-friendly elementwise + reduction work.
    a_cert, b_cert = cert[:, :, None], cert[:, None, :]
    a_src, b_src = src[:, :, None], src[:, None, :]
    a_due, b_due = due[:, :, None], due[:, None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    cert_eq = a_cert == b_cert
    src_eq = a_src == b_src
    lt = (
        (a_cert < b_cert)
        | (cert_eq & (a_src < b_src))
        | (cert_eq & src_eq & (a_due < b_due))
        | (cert_eq & src_eq & (a_due == b_due) & (ii < jj)[None])
    )
    rank = jnp.sum(lt.astype(jnp.int32), axis=1)  # (tw, n) predecessors of col j

    # scatter-by-rank: output column c takes the unique element of rank
    # c (one-hot select + sum — exact for ints and for +inf certs)
    sel = rank[:, None, :] == jax.lax.broadcasted_iota(jnp.int32, (1, cap, n), 1)
    o_cert_ref[...] = jnp.sum(jnp.where(sel, cert[:, None, :], 0.0), axis=2)
    o_due_ref[...] = jnp.sum(jnp.where(sel, due[:, None, :], 0), axis=2)
    o_src_ref[...] = jnp.sum(jnp.where(sel, src[:, None, :], 0), axis=2)
    o_slot_ref[...] = jnp.sum(jnp.where(sel, slot[:, None, :], 0), axis=2)


@functools.partial(jax.jit, static_argnames=("tile_w", "interpret"))
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
    """Sparse-control candidate-list ingest: merge the (W, m) candidate
    block into the (W, C) pending queues, keeping the lexicographically
    smallest C per row by (cert, src, due) — worst-certificate-first
    eviction. Bit-identical to ``kernels/ref.py::queue_ingest_ref``
    (pinned in tests/test_kernels.py).

    Args:
        q_cert/q_due/q_src/q_slot: (W, C) PendingQueue leaves.
        c_cert/c_due/c_src/c_slot: (W, m) candidate block — +inf cert /
            due -1 marks an invalid (padded or self/OOB) candidate.
        tile_w: destination rows per grid step.
        interpret: ``None`` follows the platform (compiled on a TPU,
            interpreted elsewhere).

    Returns ``(q_cert', q_due', q_src', q_slot')``, each (W, C).
    """
    w, cap = q_cert.shape
    m = c_cert.shape[1]
    w_pad = -w % tile_w
    if w_pad:
        q_cert = jnp.pad(q_cert, ((0, w_pad), (0, 0)), constant_values=jnp.inf)
        q_due = jnp.pad(q_due, ((0, w_pad), (0, 0)), constant_values=-1)
        q_src = jnp.pad(q_src, ((0, w_pad), (0, 0)))
        q_slot = jnp.pad(q_slot, ((0, w_pad), (0, 0)))
        c_cert = jnp.pad(c_cert, ((0, w_pad), (0, 0)), constant_values=jnp.inf)
        c_due = jnp.pad(c_due, ((0, w_pad), (0, 0)), constant_values=-1)
        c_src = jnp.pad(c_src, ((0, w_pad), (0, 0)))
        c_slot = jnp.pad(c_slot, ((0, w_pad), (0, 0)))
    steps = q_cert.shape[0] // tile_w

    row = lambda i: (i, 0)  # noqa: E731
    queue_spec = pl.BlockSpec((tile_w, cap), row)
    cand_spec = pl.BlockSpec((tile_w, m), row)
    out = pl.pallas_call(
        _queue_ingest_kernel,
        grid=(steps,),
        in_specs=[queue_spec] * 4 + [cand_spec] * 4,
        out_specs=[queue_spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((w + w_pad, cap), jnp.float32),
            jax.ShapeDtypeStruct((w + w_pad, cap), jnp.int32),
            jax.ShapeDtypeStruct((w + w_pad, cap), jnp.int32),
            jax.ShapeDtypeStruct((w + w_pad, cap), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(q_cert, q_due, q_src, q_slot, c_cert, c_due, c_src, c_slot)
    return tuple(a[:w] for a in out)


@functools.partial(jax.jit, static_argnames=("eps", "tile_w", "interpret"))
def round_step(
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
    """Fused sparse delivery + accept + credit; see the module docstring.

    Args:
        q_cert/q_due/q_src/q_slot: (W, C) PendingQueue leaves.
        certs0: (W,) f32 current certificates.
        alive: (W,) int32 (nonzero = alive destination).
        credit: (W,) f32 compute credit before this round.
        speed_norm: (W,) f32 normalized per-worker speed.
        r: () i32 current round.
        eps: static protocol acceptance gap.
        tile_w: destination rows per grid step.
        interpret: ``None`` follows the platform (compiled on a TPU,
            interpreted elsewhere).

    Returns ``(q_cert', best_cert, best_src, best_slot, take, n_arr,
    credit', active)`` — (W, C) and seven (W,) arrays; ``take`` and
    ``active`` are int32 masks.
    """
    w, cap = q_cert.shape
    w_pad = -w % tile_w
    if w_pad:
        q_cert = jnp.pad(q_cert, ((0, w_pad), (0, 0)), constant_values=jnp.inf)
        q_due = jnp.pad(q_due, ((0, w_pad), (0, 0)), constant_values=-1)
        q_src = jnp.pad(q_src, ((0, w_pad), (0, 0)))
        q_slot = jnp.pad(q_slot, ((0, w_pad), (0, 0)))
        certs0 = jnp.pad(certs0, (0, w_pad))
        alive = jnp.pad(alive, (0, w_pad))
        credit = jnp.pad(credit, (0, w_pad))
        speed_norm = jnp.pad(speed_norm, (0, w_pad))
    steps = q_cert.shape[0] // tile_w

    row = lambda i: (i, 0)  # noqa: E731
    rep = lambda i: (0, 0)  # noqa: E731
    vec_spec = pl.BlockSpec((tile_w, 1), row)
    out = pl.pallas_call(
        functools.partial(_round_step_kernel, eps=eps),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((tile_w, cap), row),
            pl.BlockSpec((tile_w, cap), row),
            pl.BlockSpec((tile_w, cap), row),
            pl.BlockSpec((tile_w, cap), row),
            vec_spec,
            vec_spec,
            vec_spec,
            vec_spec,
            pl.BlockSpec((1, 1), rep),
        ],
        out_specs=[
            pl.BlockSpec((tile_w, cap), row),
            vec_spec,
            vec_spec,
            vec_spec,
            vec_spec,
            vec_spec,
            vec_spec,
            vec_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w + w_pad, cap), jnp.float32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((w + w_pad, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(
        q_cert,
        q_due,
        q_src,
        q_slot,
        certs0.reshape(-1, 1),
        alive.reshape(-1, 1).astype(jnp.int32),
        credit.reshape(-1, 1),
        speed_norm.reshape(-1, 1),
        r.reshape(1, 1).astype(jnp.int32),
    )
    q_cert_new, best_cert, best_src, best_slot, take, n_arr, credit_new, active = out
    trim = lambda a: a[:w, 0]  # noqa: E731
    return (
        q_cert_new[:w],
        trim(best_cert),
        trim(best_src),
        trim(best_slot),
        trim(take),
        trim(n_arr),
        trim(credit_new),
        trim(active),
    )
