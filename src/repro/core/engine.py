"""Vectorized round-based TMSN engine (fidelity level 2).

The event-driven :class:`~repro.core.simulator.TMSNSimulator` is the
fidelity-1 oracle: exact per-event ordering, continuous latencies, one
Python heap pop (and one small JAX dispatch) per worker segment. That
is faithful but interpreter-bound — past ~16 workers the wall clock is
all Python, which puts the paper's actual regime (hundreds of machines,
resilience that only shows at scale) out of reach.

This engine trades event fidelity for a *round* abstraction that keeps
every worker on the device at once:

  * all W workers carry their state as stacked ``(W, ...)`` arrays and
    advance one scheduling segment per round inside a single jitted
    computation (``vmap`` over the worker axis);
  * gossip is a masked exchange step — per-link latencies are quantized
    to integer round delays and carried in a ``(W, W, D)`` in-flight
    certificate buffer (``inflight[dst, src, d]`` = certificate of a
    message from ``src`` reaching ``dst`` in ``d`` more rounds), with
    model payloads looked up in a ``(D, W)`` snapshot ring;
  * ``accepts`` / ``improves`` from :mod:`repro.core.protocol` are
    applied elementwise, so fail-stop is a boolean mask and laggards
    are a per-worker speed vector driving a compute-credit accumulator
    (a 0.25-speed worker completes a segment every 4th round).

Round order (matches the event sim under zero latency + uniform speed:
a message broadcast during round ``r`` is applied to every receiver
*before* its round ``r+1`` segment):

  1. deliver arrivals due this round (adopt the best accepted message),
  2. shift the in-flight buffer,
  3. run one segment per live, credit-covered worker (resample-flagged
     workers spend their segment on the batched resample path),
  4. broadcast certificates that strictly improved,
  5. snapshot every worker's model into the ring.

The engine returns the same :class:`~repro.core.result.SimResult` as
the simulator, so benchmarks and analysis are substrate-agnostic.

Dispatch chunking: at small per-round compute the wall clock is one
Python dispatch + one host sync *per round*. The engine therefore runs
:attr:`EngineConfig.rounds_per_dispatch` rounds per jitted call inside
a ``lax.while_loop``, returning the per-round :class:`RoundInfo` stacked
over the chunk — one dispatch and at most one device sync per chunk,
while per-round history and the *exact* round that crossed
``target_certificate`` are still recovered on the host. When a target
is set, the loop stops on the crossing round, so the final state is
bit-identical to an unchunked (``rounds_per_dispatch=1``) run for
every chunk size.

Fidelity level 3 — the device-sharded substrate: when
:attr:`EngineConfig.mesh` names a multi-device ``workers`` mesh,
:func:`make_engine` returns a
:class:`~repro.core.engine_sharded.ShardedTMSNEngine` that partitions
the stacked ``(W, ...)`` worker state over the mesh with ``shard_map``.
Each device advances only its ``W_local = W / n_dev`` workers per
round; the ``(W, W, D)`` in-flight buffer becomes a per-shard
``(W_local, W, D)`` slice (destination-sharded), and gossip is one
explicit ``all_gather`` of the round's certificates and model payloads
— O(W·payload) traffic per round instead of replicated global state,
or O(n_dev·k·payload) under :attr:`EngineConfig.gossip_mode` "gated",
where only each device's top-k locally-improved candidates ship their
model. :attr:`EngineConfig.control_plane` "sparse" applies the same
idea to the control plane itself: instead of the dense per-round (W,)
certificate + flag all_gather, the exchange carries only (cert,
global_id, round) triples for those top-k candidates — a fixed-size
(n_dev, k) gather scattered into the in-flight state by global id, so
per-round gossip cost is O(n_dev·k), independent of W.
The equivalence contract is strict: on identical configs and seeds the
sharded engine must produce the *same final certificates* as this
single-device engine (which PR 1 in turn pins against the event-driven
fidelity-1 oracle), including fail-stop masks and laggard credit;
``tests/test_sharded_engine.py`` enforces it on 8 forced host devices.

One rung further, a 2-D ``("pod", "workers")`` mesh makes the gossip
hierarchical: per-round all_gathers stay inside a pod (ICI) while only
each device's freshest top-k pending improvements cross the ``pod``
axis (DCN) every :attr:`EngineConfig.cross_pod_every_k` rounds —
bit-identical to the flat engine at ``k=1`` under uniform delay, a
benchmark-measured approximation beyond.

The worker contract this engine drives —
:class:`repro.core.worker.BatchedTMSNWorker` — lives in
:mod:`repro.core.worker` (imported here for backward compatibility);
this module only *consumes* it, through the optional-hook helpers in
that module, and never references any concrete worker type.

Sharding contract: everything in this module is written to be
shardable over the worker axis — every per-worker quantity (including
per-worker constants like feature-ownership masks) lives in the state
pytree with a leading ``(W,)`` axis and shards with it; scalars carried
in :class:`EngineState` (``round``, the counters on THIS engine) are
replicated. On the single-device engine the distinction is vacuous;
:mod:`repro.core.engine_sharded` states the full per-shard/replicated
split its ``shard_map`` enforces.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.protocol import accepts, improves
from repro.core.result import SimResult, TrafficCounters
from repro.core.worker import (
    BatchedTMSNWorker,
    bind_shared_data,
    has_resample_hooks,
    resolve_payload_bytes,
    shared_data,
)

#: multiplier applied to the warm-up probe's measured
#: ``inflight_occupancy_peak`` when ``inflight_capacity="auto"`` sizes
#: the pending queues — headroom for occupancy growth past the probe
#: window (e.g. laggards catching up, delay tails filling in)
AUTO_CAPACITY_HEADROOM = 2.0


def _env_int(name: str, default: int, special: tuple[str, ...] = ()) -> int | str:
    """Integer ``REPRO_*`` override: unset/empty/whitespace falls back
    to the default; a malformed value raises naming the variable (the
    bare ``int()`` error would not say where the bad string came from).
    ``special`` whitelists non-integer sentinel values (e.g. ``"auto"``
    for REPRO_INFLIGHT_CAPACITY) that pass through verbatim."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    if raw.lower() in special:
        return raw.lower()
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"env override {name} must be an integer, got {raw!r}") from None


def _env_str(name: str, default: str) -> str:
    """String ``REPRO_*`` override; unset/empty/whitespace = default.
    Value validation stays with the consumer (TMSNEngine rejects unknown
    gossip modes whether they came from the env or an explicit arg)."""
    raw = os.environ.get(name, "").strip()
    return raw if raw else default


def _env_float(name: str, default: float) -> float:
    """Float ``REPRO_*`` override: unset/empty/whitespace falls back to
    the default; a malformed value raises naming the variable (same
    contract as :func:`_env_int`)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"env override {name} must be a float, got {raw!r}") from None


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Adversarial message-fault schedule, applied at the gossip
    boundary (the push side of the in-flight state) inside the jitted
    round step of both engines.

    Every mask is drawn from a counter-based hash of ``(round, dst gid,
    src gid, seed, salt)`` — no carried PRNG state, so the same plan
    produces bit-identical faults on every substrate and sharding
    (single-device, sharded, pod mesh), which is what lets
    ``tests/test_chaos.py`` pin cross-substrate equivalence *under*
    faults. Probabilities are per directed edge per round.

    Exact-vs-measured status per field (see docs/architecture.md for
    the arguments): ``drop_prob``/``duplicate_prob`` are EXACT no-ops on
    the final certificates under uniform delay (given adequate queue
    capacity); ``corrupt_prob`` is EXACT (every corrupt certificate is
    rejected by the eps-gate soundness check); ``reorder_max`` and the
    partition window are MEASURED approximations (bench_scaling.py
    chaos section)."""

    #: per-edge probability a pushed message is silently lost
    drop_prob: float = 0.0
    #: per-edge probability a pushed message is enqueued twice
    #: (idempotent no-op on the dense (W, W, D) buffer — same cell
    #: written twice — so only the queue paths see extra entries)
    duplicate_prob: float = 0.0
    #: bounded reorder: delivery round jittered by +U{0..reorder_max},
    #: clamped to push_round + ring depth so the payload snapshot is
    #: still live at delivery. Queue-only (the dense buffer derives the
    #: ring slot from the static delay matrix, so late delivery would
    #: fetch a wrong-generation payload) — the engine rejects
    #: ``reorder_max > 0`` with ``inflight_capacity == 0``.
    reorder_max: int = 0
    #: per-edge probability the pushed certificate is corrupted
    #: (rotating NaN / -inf / +1e6 by hash) — always caught by the
    #: soundness check, accounted in ``messages_corrupt_rejected``
    corrupt_prob: float = 0.0
    seed: int = 0
    #: DCN pod partition: drop EVERY cross-pod edge for rounds in
    #: ``[partition_start, partition_stop)``. Inert off the pod mesh
    #: (no pod geometry => no cross-pod edges). -1/-1 = disabled.
    partition_start: int = -1
    partition_stop: int = -1

    @property
    def active(self) -> bool:
        return (
            self.drop_prob > 0.0
            or self.duplicate_prob > 0.0
            or self.reorder_max > 0
            or self.corrupt_prob > 0.0
            or (0 <= self.partition_start < self.partition_stop)
        )


@dataclasses.dataclass(frozen=True)
class MembershipPlan:
    """Elastic-membership schedule: mid-run joins into pre-allocated
    spare slots, plus leaves (folded into the fail-stop mask).

    ``joins`` holds ``(round, slot)`` pairs with 1-BASED rounds: a join
    at round ``k`` makes the spare's first live round the k-th round of
    the run, so ``k=1`` is provably bit-identical to a run where that
    worker was simply never masked out (the exact pin in
    tests/test_chaos.py). Slots must lie in the spare region
    ``[n_workers - spare_slots, n_workers)`` — spares are allocated (and
    compiled) up front, so activation never recompiles. On activation
    the spare's laggard credit is reseeded to zero (its credit
    accumulator ran while masked) and its batch-stream PRNG key is its
    untouched ``init_batch`` stream (masked rows are bitwise unchanged
    by the worker contract); it adopts the current best certificate
    through the ordinary gossip/accept machinery on its first arrival.

    ``leaves`` holds ``(round, worker)`` pairs, folded into
    ``fail_round`` via min — join + leave composes into churn traces."""

    joins: tuple = ()
    leaves: tuple = ()


def _parse_fault_spec(spec: str) -> FaultPlan | None:
    """Parse the ``REPRO_FAULT_PLAN`` spec string, e.g.
    ``"drop=5,dup=2,corrupt=2,reorder=1,seed=9,part=8:16"`` —
    probabilities in integer PERCENT, ``part`` a ``start:stop`` round
    window. Empty/whitespace = no plan. Malformed values raise naming
    the variable (same contract as ``_env_int``)."""
    spec = spec.strip()
    if not spec:
        return None
    kw: dict[str, Any] = {}
    for field in spec.split(","):
        field = field.strip()
        if not field:
            continue
        key, sep, val = field.partition("=")
        key, val = key.strip().lower(), val.strip()
        if not sep:
            raise ValueError(
                f"env override REPRO_FAULT_PLAN: expected key=value, got {field!r}"
            )
        try:
            if key in ("drop", "dup", "corrupt"):
                pct = int(val)
                if not 0 <= pct <= 100:
                    raise ValueError(
                        f"env override REPRO_FAULT_PLAN: field {key!r} is a "
                        f"percentage and must be in [0, 100], got {pct}"
                    )
                dest = {"drop": "drop_prob", "dup": "duplicate_prob",
                        "corrupt": "corrupt_prob"}[key]
                kw[dest] = pct / 100.0
            elif key == "reorder":
                kw["reorder_max"] = int(val)
            elif key == "seed":
                kw["seed"] = int(val)
            elif key == "part":
                a, _, b = val.partition(":")
                kw["partition_start"] = int(a)
                kw["partition_stop"] = int(b)
            else:
                raise ValueError(
                    f"env override REPRO_FAULT_PLAN: unknown field {key!r} "
                    f"(known: drop, dup, corrupt, reorder, seed, part)"
                )
        except ValueError as e:
            if "REPRO_FAULT_PLAN" in str(e):
                raise
            raise ValueError(
                f"env override REPRO_FAULT_PLAN: field {key!r} must be an "
                f"integer, got {val!r}"
            ) from None
    plan = FaultPlan(**kw)
    # An all-zero spec is a clean run: normalize to None so the engine
    # keeps the exact clean-path computation graph.
    return plan if plan.active else None


def _fault_hash(r, dst, src, seed: int, salt: int):
    """Counter-based per-edge uint32 hash (murmur-style finalizer) over
    ``(round, dst gid, src gid, plan seed, salt)``. Stateless and
    elementwise, so the masks it seeds are independent of sharding,
    substrate, and evaluation order — the property every
    cross-substrate-under-faults pin rests on."""
    x = (
        r.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + dst.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + src.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
        + jnp.uint32((seed * 0x27D4EB2F + salt * 0x165667B1) & 0xFFFFFFFF)
    )
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _fault_unit(r, dst, src, seed: int, salt: int):
    """Uniform [0, 1) f32 per (round, dst, src) edge."""
    return _fault_hash(r, dst, src, seed, salt).astype(jnp.float32) * jnp.float32(
        1.0 / 4294967296.0
    )


def _inject_faults(
    plan: FaultPlan,
    pod_of,
    r,
    dst_gids,
    src_gids,
    cert,
    due,
    dst_cert,
    depth: int,
):
    """Apply a :class:`FaultPlan` to one round's push candidates.

    ``cert`` is (W_local, m) f32 with +inf marking invalid entries —
    the common currency of every push path; ``src_gids`` is (W_local, m)
    i32 global source ids, ``dst_gids`` (W_local,) global destination
    ids, ``dst_cert`` (W_local,) the destinations' current (post-scan)
    certificates, ``due`` (W_local, m) i32 absolute delivery rounds or
    ``None`` on the dense-buffer paths (which cannot reorder).

    Order: drop (incl. pod partition) -> corrupt -> eps-gate soundness
    check -> due jitter -> duplicate mask. The soundness check rejects
    any candidate whose certificate is non-finite or >= the
    destination's current certificate: destination certificates are
    monotone non-increasing (worker contract), so an incoming cert
    ``>= cert_now`` can never satisfy the strict accept gate
    ``incoming < cert_later - eps`` for any eps >= 0 — rejection is
    provably harmless to the final certificates while keeping every
    corrupt value out of the pending queues.

    Returns ``(cert, due, dup_mask, n_dropped, n_rejected)`` — the
    caller turns ``dup_mask`` into extra queue entries (queue paths) or
    ignores it (dense buffer, where a duplicate write is a no-op)."""
    valid0 = jnp.isfinite(cert)
    dst2 = dst_gids[:, None]
    seed = int(plan.seed)
    drop = jnp.zeros(cert.shape, bool)
    if plan.drop_prob > 0.0:
        drop = _fault_unit(r, dst2, src_gids, seed, 1) < jnp.float32(plan.drop_prob)
    if pod_of is not None and 0 <= plan.partition_start < plan.partition_stop:
        in_window = (r >= plan.partition_start) & (r < plan.partition_stop)
        cross = pod_of[dst_gids][:, None] != pod_of[src_gids]
        drop = drop | (cross & in_window)
    drop = drop & valid0
    n_dropped = jnp.sum(drop, dtype=jnp.int32)

    live = valid0 & ~drop
    if plan.corrupt_prob > 0.0:
        cor = live & (
            _fault_unit(r, dst2, src_gids, seed, 2) < jnp.float32(plan.corrupt_prob)
        )
        sel = _fault_hash(r, dst2, src_gids, seed, 3) % jnp.uint32(3)
        bad = jnp.where(
            sel == 0,
            jnp.float32(jnp.nan),
            jnp.where(sel == 1, -jnp.inf, cert + jnp.float32(1e6)),
        )
        cert = jnp.where(cor, bad, cert)
    # eps-gate soundness check: reject non-finite / non-improving certs
    # before they can poison the pending state
    unsound = live & (~jnp.isfinite(cert) | (cert >= dst_cert[:, None]))
    n_rejected = jnp.sum(unsound, dtype=jnp.int32)

    keep = live & ~unsound
    cert = jnp.where(keep, cert, jnp.inf)
    if due is not None:
        if plan.reorder_max > 0:
            jit = (
                _fault_hash(r, dst2, src_gids, seed, 4)
                % jnp.uint32(plan.reorder_max + 1)
            ).astype(jnp.int32)
            due = jnp.minimum(due + jit, r + depth)
        due = jnp.where(keep, due, -1)
    dup = jnp.zeros(cert.shape, bool)
    if plan.duplicate_prob > 0.0:
        dup = keep & (
            _fault_unit(r, dst2, src_gids, seed, 5) < jnp.float32(plan.duplicate_prob)
        )
    return cert, due, dup, n_dropped, n_rejected


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_workers: int = 4
    eps: float = 0.0  # protocol gap; gates ACCEPTANCE only (as in the sim)
    max_rounds: int = 1000
    #: per-link broadcast latency in ROUNDS: an int (uniform) or a
    #: (W, W) ``delay[src, dst]`` integer array, clipped to >= 1. A
    #: message sent during round r is delivered at round r + delay.
    delay_rounds: Any = 1
    #: per-worker speed, cost units per simulated second; also drives
    #: the round-level compute credit (normalized to the fastest
    #: worker). None = uniform.
    speed: Any = None
    #: round index at which each worker fail-stops (None = never).
    fail_round: Any = None
    target_certificate: float | None = None
    seed: int = 0
    #: record per-worker certificate changes into SimResult.history
    record_history: bool = True
    #: rounds advanced per jitted dispatch (one loop per chunk). 1 =
    #: the old one-dispatch-per-round behavior; larger chunks amortize
    #: Python dispatch + host sync without changing any protocol
    #: semantics (exact rounds-to-target and per-round history are
    #: recovered from the stacked per-round info). Env-overridable so
    #: CI can rerun the whole tier chunked: REPRO_ROUNDS_PER_DISPATCH.
    rounds_per_dispatch: int = dataclasses.field(
        default_factory=lambda: _env_int("REPRO_ROUNDS_PER_DISPATCH", 8)
    )
    #: cross-device gossip policy of the SHARDED engine (ignored on one
    #: device). "dense": all_gather every worker's model payload every
    #: round — O(W·payload) on the wire. "gated": all_gather only the
    #: cheap certificates + broadcast flags (W·5 bytes) densely; model
    #: payloads move only for each device's top-``gossip_top_k``
    #: locally-improved candidates — O(n_dev·k·payload). The eps gate
    #: still applies to ACCEPTANCE only; gating shapes traffic via the
    #: improvement test. Under uniform delay gated mode adopts models
    #: identical to dense mode (the per-round argmin is always among
    #: per-shard minima — pinned in tests/test_sharded_engine.py);
    #: under heterogeneous delay matrices it is an explicit
    #: approximation. Env-overridable: REPRO_GOSSIP_MODE.
    gossip_mode: str = dataclasses.field(
        default_factory=lambda: _env_str("REPRO_GOSSIP_MODE", "dense")
    )
    #: per-device candidate count for gated gossip (clamped to the
    #: shard's local worker count)
    gossip_top_k: int = 1
    #: cross-pod exchange cadence of the pod-mesh engine, in rounds
    #: (ignored without a ``pod`` mesh axis). 1 = flush the cross-pod
    #: tier every round, which under UNIFORM delay reproduces the flat
    #: single-axis engine bit-identically (pinned in
    #: tests/test_sharded_engine.py); k > 1 lets improvements accumulate
    #: in the pending tier and ships only the freshest certificates
    #: every k-th round over the DCN — an explicit approximation,
    #: measured by bench_scaling.py. Env: REPRO_CROSS_POD_EVERY_K.
    cross_pod_every_k: int = dataclasses.field(
        default_factory=lambda: _env_int("REPRO_CROSS_POD_EVERY_K", 1)
    )
    #: per-device candidate count for each cross-pod flush (the PR 3
    #: top-k gated payload path applied to the pod axis; clamped to the
    #: shard's local worker count). Env: REPRO_CROSS_POD_TOP_K.
    cross_pod_top_k: int = dataclasses.field(
        default_factory=lambda: _env_int("REPRO_CROSS_POD_TOP_K", 1)
    )
    #: bounded per-destination pending-queue capacity C for the
    #: in-flight state. 0 (default) keeps the dense ``(W, W, D)``
    #: certificate buffer — the exact oracle. C >= 1 replaces it with a
    #: per-destination ``(W, C)`` queue of pending (cert, src, due,
    #: ring-slot) entries, evicting worst-certificate-first on
    #: overflow: O(W·C) state instead of O(W²·D). When C covers the
    #: peak per-destination occupancy the sparse run is bit-identical
    #: to the dense oracle (``SimResult.messages_evicted == 0`` is the
    #: run-level witness); smaller C is an explicit, measured
    #: approximation — see docs/config.md. ``"auto"`` sizes C from a
    #: short warm-up occupancy probe at run() time: the probe's measured
    #: ``inflight_occupancy_peak`` × ``AUTO_CAPACITY_HEADROOM``, logged
    #: into ``SimResult.inflight_capacity_selected``. Env-overridable so
    #: a CI matrix leg can rerun the tier sparse:
    #: REPRO_INFLIGHT_CAPACITY (accepts ``auto``).
    inflight_capacity: Any = dataclasses.field(
        default_factory=lambda: _env_int("REPRO_INFLIGHT_CAPACITY", 0, special=("auto",))
    )
    #: delivery implementation of the sparse path (ignored while
    #: ``inflight_capacity == 0``): "pallas" routes delivery-argmin +
    #: eps-gated accept + laggard-credit update through the fused
    #: ``kernels/round_step.py`` kernel (interpret mode off-TPU);
    #: "ref" uses the pure-jnp oracle in ``kernels/ref.py``. Both are
    #: bit-identical — pinned in tests. Env: REPRO_ROUND_STEP_IMPL.
    round_step_impl: str = dataclasses.field(
        default_factory=lambda: _env_str("REPRO_ROUND_STEP_IMPL", "pallas")
    )
    #: per-round control-plane exchange policy. "dense": every round
    #: moves a (W,) certificate (+ broadcast-flag) all_gather and the
    #: receivers scan/scatter the full width — O(W) wire and
    #: O(W_local·W) work per round even in gated gossip. "sparse": the
    #: exchange carries only each device's top-``gossip_top_k``
    #: locally-improved candidates as (cert, global_id, round) triples —
    #: a fixed-size (n_dev, k) all_gather, OOB-padded — and receivers
    #: scatter them into the pending queues / in-flight state by global
    #: id: O(n_dev·k), independent of W. Under UNIFORM delay sparse
    #: control is bit-identical to dense control (the delivery argmin is
    #: always among the per-device top improvers — pinned in
    #: tests/test_sparse_inflight.py); under heterogeneous delay it is a
    #: measured approximation (bench_scaling.py, control-plane section).
    #: Env-overridable: REPRO_CONTROL_PLANE.
    control_plane: str = dataclasses.field(
        default_factory=lambda: _env_str("REPRO_CONTROL_PLANE", "dense")
    )
    #: trailing worker rows pre-allocated as masked-out SPARES for
    #: elastic membership: they carry state and compile like any other
    #: row but start dead, so a :class:`MembershipPlan` join can
    #: activate one mid-run with zero recompilation. A spare without a
    #: scheduled join never activates. Env: REPRO_SPARE_SLOTS.
    spare_slots: int = dataclasses.field(
        default_factory=lambda: _env_int("REPRO_SPARE_SLOTS", 0)
    )
    #: optional :class:`MembershipPlan` (joins into spare slots, leaves
    #: folded into ``fail_round``); programmatic only — schedules are
    #: structured data, not an env knob.
    membership: Any = None
    #: adversarial fault schedule at the gossip boundary: a
    #: :class:`FaultPlan` (programmatic, wins) or the
    #: ``REPRO_FAULT_PLAN`` spec string parsed by
    #: :func:`_parse_fault_spec` (e.g. ``"drop=5,corrupt=2,seed=9"``,
    #: integer percent). Empty = no injection, bit-identical clean
    #: semantics. The CI chaos leg drives this via the env; tests that
    #: pin engine-vs-oracle equivalence set ``fault_spec=""`` explicitly
    #: so the leg only steers env-following runs (same convention as
    #: the other matrix knobs). Env: REPRO_FAULT_PLAN.
    fault_spec: str = dataclasses.field(
        default_factory=lambda: _env_str("REPRO_FAULT_PLAN", "")
    )
    fault_plan: Any = None
    #: serving publish gate, in rounds: with a publisher attached
    #: (:meth:`TMSNEngine.attach_publisher`), the engine checks the
    #: ensemble's best certificate at the first chunk boundary at or
    #: after every k-th round and publishes that worker's model into
    #: the adoption slot when it improved. 0 (default) disables the
    #: check entirely — the clean engine takes no extra host syncs.
    #: Publishing is host-side and outside the jitted round step, so
    #: the protocol semantics and the compiled graph are unchanged
    #: either way. Env: REPRO_PUBLISH_EVERY_K.
    publish_every_k: int = dataclasses.field(
        default_factory=lambda: _env_int("REPRO_PUBLISH_EVERY_K", 0)
    )
    #: minimum best-certificate improvement (strict, in certificate
    #: units) over the previously published snapshot before a new one
    #: is published — the serving-edge analogue of the protocol's
    #: broadcast-on-improvement gate. 0.0 publishes on any strict
    #: improvement. Env: REPRO_PUBLISH_EPS.
    publish_eps: float = dataclasses.field(
        default_factory=lambda: _env_float("REPRO_PUBLISH_EPS", 0.0)
    )
    #: optional ``jax.sharding.Mesh``: a 1-D ``("workers",)`` mesh
    #: shards the worker axis over one interconnect tier; a 2-D
    #: ``("pod", "workers")`` mesh adds the hierarchical cross-pod tier
    #: (``launch/mesh.py::make_worker_mesh(pods=...)`` builds both).
    #: ``None`` or a 1-device mesh keeps the single-device path; a
    #: multi-device mesh makes :func:`make_engine` build the
    #: shard-mapped engine (``n_workers`` must divide evenly over the
    #: total device count).
    mesh: Any = None


class PendingQueue(NamedTuple):
    """Bounded per-destination pending-message state (the sparse
    replacement for the dense ``(W, W, D)`` in-flight buffer when
    :attr:`EngineConfig.inflight_capacity` > 0).

    Each destination row holds up to C pending messages; ``cert`` is
    +inf on empty slots. ``due`` is the ABSOLUTE delivery round, so a
    delivered entry only needs its cert cleared — a stale ``due`` can
    never match a later (monotonically increasing) round. ``slot`` is
    the snapshot-ring slot captured at push time (``push_round % D``),
    which equals the dense engine's payload lookup
    ``(r - delay[src, dst]) % D`` at delivery."""

    cert: jnp.ndarray  # (W, C) f32; +inf = empty
    src: jnp.ndarray  # (W, C) i32 global source worker id
    due: jnp.ndarray  # (W, C) i32 absolute delivery round (-1 = empty)
    slot: jnp.ndarray  # (W, C) i32 ring slot of the payload


def _empty_queue(w: int, capacity: int) -> PendingQueue:
    return PendingQueue(
        cert=jnp.full((w, capacity), jnp.inf, jnp.float32),
        src=jnp.zeros((w, capacity), jnp.int32),
        due=jnp.full((w, capacity), -1, jnp.int32),
        slot=jnp.zeros((w, capacity), jnp.int32),
    )


def _queue_push(
    queue: PendingQueue,
    score: jnp.ndarray,
    alive: jnp.ndarray,
    local_gids: jnp.ndarray,
    delay_rows: jnp.ndarray,
    r: jnp.ndarray,
    depth: int,
    dst_cert: jnp.ndarray | None = None,
    fault: FaultPlan | None = None,
    pod_of=None,
) -> tuple[PendingQueue, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Push this round's broadcast candidates into every local
    destination's pending queue, evicting worst-certificate-first.

    ``score`` is (W,) f32 over GLOBAL source ids: the candidate's
    certificate where that source broadcasts this round, +inf where it
    does not. This one shape serves every call site — single-device
    (``where(improved, certs, inf)``), the sharded tier-1 control plane
    (always dense-width, both gossip modes), and the pod-mesh cross-pod
    flush. ``alive`` (W_local,) masks destinations, ``local_gids``
    (W_local,) are the destinations' global ids (self-exclusion),
    ``delay_rows`` is (W_local, W) indexed [local dst, global src].

    Candidate pre-filter: only the globally best ``C + 1`` candidates
    can ever enter a kept top-C (a candidate ranked below C + 1 has at
    least C better non-self competitors at every destination), so the
    merge sorts (W_local, C + min(C+1, W)) instead of (W_local, C + W).
    Eviction keeps the lexicographically smallest C by (cert, src, due)
    — worst-certificate-first, ties dropping the higher source id, so
    the survivor set always contains every entry the dense delivery
    argmin could select.

    Returns ``(queue, n_pushed, n_evicted, occ_pre_max)``. The counters
    are LOGICAL (capacity-independent): ``n_pushed`` equals the dense
    engine's ``sum(push_mask)``; ``n_evicted`` counts every candidate
    offered but not retained (including pre-filtered ones — if anything
    was pre-filtered the queue provably fills to C, so the accounting
    stays exact); ``occ_pre_max`` is the peak pre-eviction occupancy.
    ``n_evicted == 0`` over a whole run certifies the sparse run as
    bit-identical to the dense oracle.

    With ``fault`` set, :func:`_inject_faults` runs on the candidate
    block before the merge (the pre-filter is applied PRE-fault, so its
    top-``C+1`` window is the clean run's); duplicates become extra
    candidate columns, and the occupancy/eviction accounting switches
    from the logical offer count to the post-fault effective one (a
    dropped message must not read as an eviction). Two extra counters
    ``(n_dropped, n_rejected)`` join the return tuple — zero when
    ``fault`` is None.
    """
    w = score.shape[0]
    wl, cap = queue.cert.shape
    k = min(cap + 1, w)
    order = jnp.argsort(score, stable=True)[:k].astype(jnp.int32)
    c_cert = score[order]  # (k,) sorted best candidates
    val = (
        jnp.isfinite(c_cert)[None, :]
        & (order[None, :] != local_gids[:, None])
        & alive[:, None]
    )
    cand_cert = jnp.where(val, c_cert[None, :], jnp.inf)  # (wl, k)
    cand_src = jnp.broadcast_to(order[None, :], (wl, k))
    cand_due = jnp.where(
        val, r + jnp.take_along_axis(delay_rows, cand_src, axis=1), -1
    )
    cand_slot = jnp.where(val, jnp.int32(r % depth), 0)

    n_dropped = jnp.zeros((), jnp.int32)
    n_rejected = jnp.zeros((), jnp.int32)
    if fault is not None:
        cand_cert, cand_due, dup, n_dropped, n_rejected = _inject_faults(
            fault, pod_of, r, local_gids, cand_src, cand_cert, cand_due,
            dst_cert, depth,
        )
        if fault.duplicate_prob > 0.0:
            cand_cert = jnp.concatenate(
                [cand_cert, jnp.where(dup, cand_cert, jnp.inf)], axis=1
            )
            cand_src = jnp.concatenate([cand_src, cand_src], axis=1)
            cand_due = jnp.concatenate(
                [cand_due, jnp.where(dup, cand_due, -1)], axis=1
            )
            cand_slot = jnp.concatenate([cand_slot, cand_slot], axis=1)

    m_cert = jnp.concatenate([queue.cert, cand_cert], axis=1)
    m_src = jnp.concatenate([queue.src, cand_src], axis=1)
    m_due = jnp.concatenate([queue.due, cand_due], axis=1)
    m_slot = jnp.concatenate([queue.slot, cand_slot], axis=1)
    keep = jnp.lexsort((m_due, m_src, m_cert), axis=-1)[:, :cap]
    new = PendingQueue(
        cert=jnp.take_along_axis(m_cert, keep, axis=1),
        src=jnp.take_along_axis(m_src, keep, axis=1),
        due=jnp.take_along_axis(m_due, keep, axis=1),
        slot=jnp.take_along_axis(m_slot, keep, axis=1),
    )

    n_bcast = jnp.sum(jnp.isfinite(score), dtype=jnp.int32)
    self_b = jnp.isfinite(score[local_gids]).astype(jnp.int32)
    n_cand = jnp.where(alive, n_bcast - self_b, 0)  # (wl,) logical offers
    if fault is not None:
        # occupancy math must use what actually reached the merge, or a
        # fault-dropped message would be double-counted as an eviction
        n_off = jnp.sum(jnp.isfinite(cand_cert), axis=1, dtype=jnp.int32)
    else:
        n_off = n_cand
    occ_pre = jnp.sum(jnp.isfinite(queue.cert), axis=1, dtype=jnp.int32) + n_off
    occ_after = jnp.sum(jnp.isfinite(new.cert), axis=1, dtype=jnp.int32)
    return (
        new,
        jnp.sum(n_cand, dtype=jnp.int32),
        jnp.sum(occ_pre - occ_after, dtype=jnp.int32),
        jnp.max(occ_pre),
        n_dropped,
        n_rejected,
    )


def _candidate_valid(
    cand_cert: jnp.ndarray,
    cand_ids: jnp.ndarray,
    alive: jnp.ndarray,
    local_gids: jnp.ndarray,
    w: int,
) -> jnp.ndarray:
    """(W_local, m) validity of each sparse-control candidate at each
    local destination: finite cert, in-range global id (OOB padding from
    the fixed-size all_gather carries id >= W), not the destination
    itself, destination alive."""
    return (
        jnp.isfinite(cand_cert)[None, :]
        & (cand_ids[None, :] != local_gids[:, None])
        & (cand_ids[None, :] < w)
        & alive[:, None]
    )


def _queue_push_candidates(
    queue: PendingQueue,
    cand_cert: jnp.ndarray,
    cand_ids: jnp.ndarray,
    alive: jnp.ndarray,
    local_gids: jnp.ndarray,
    delay_rows: jnp.ndarray,
    r: jnp.ndarray,
    depth: int,
    impl: str,
    dst_cert: jnp.ndarray | None = None,
    fault: FaultPlan | None = None,
    pod_of=None,
) -> tuple[PendingQueue, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse-control ingest: merge an explicit candidate list into the
    pending queues, evicting worst-certificate-first.

    Unlike :func:`_queue_push` (which scans a dense (W,) score vector),
    the candidates arrive as parallel (m,) arrays of certificates and
    global source ids — the payload of the (n_dev, k) control-plane
    all_gather, OOB-padded with ``id >= W`` / +inf certs. The merge runs
    through the candidate-list ingest kernel (``impl`` picks the Pallas
    kernel in ``kernels/round_step.py`` or the jnp reference in
    ``kernels/ref.py``; bit-identical by contract) under the same total
    order as :func:`_queue_push`'s lexsort, so the survivor set is
    identical to a dense-score push restricted to these candidates.

    Returns ``(queue, n_pushed, n_evicted, occ_pre_max, n_dropped,
    n_rejected)`` with the same counter semantics as :func:`_queue_push`
    (no pre-filter here, so every offered candidate is accounted
    directly; the trailing fault counters are zero without a plan).
    """
    w = delay_rows.shape[1]
    wl, m = delay_rows.shape[0], cand_ids.shape[0]
    ids_c = jnp.clip(cand_ids, 0, w - 1).astype(jnp.int32)
    val = _candidate_valid(cand_cert, cand_ids, alive, local_gids, w)
    c_cert = jnp.where(val, cand_cert[None, :], jnp.inf)
    c_src = jnp.broadcast_to(ids_c[None, :], (wl, m))
    c_due = jnp.where(val, r + jnp.take_along_axis(delay_rows, c_src, axis=1), -1)
    c_slot = jnp.where(val, jnp.int32(r % depth), 0)
    n_dropped = jnp.zeros((), jnp.int32)
    n_rejected = jnp.zeros((), jnp.int32)
    if fault is not None:
        c_cert, c_due, dup, n_dropped, n_rejected = _inject_faults(
            fault, pod_of, r, local_gids, c_src, c_cert, c_due, dst_cert, depth
        )
        if fault.duplicate_prob > 0.0:
            c_cert = jnp.concatenate([c_cert, jnp.where(dup, c_cert, jnp.inf)], axis=1)
            c_src = jnp.concatenate([c_src, c_src], axis=1)
            c_due = jnp.concatenate([c_due, jnp.where(dup, c_due, -1)], axis=1)
            c_slot = jnp.concatenate([c_slot, c_slot], axis=1)
    if impl == "ref":
        from repro.kernels.ref import queue_ingest_ref as ingest
    else:
        from repro.kernels.ops import queue_ingest as ingest
    q_cert, q_due, q_src, q_slot = ingest(
        queue.cert, queue.due, queue.src, queue.slot, c_cert, c_due, c_src, c_slot
    )
    new = PendingQueue(cert=q_cert, src=q_src, due=q_due, slot=q_slot)
    n_cand = jnp.sum(val, axis=1, dtype=jnp.int32)  # (wl,) offers
    if fault is not None:
        n_off = jnp.sum(jnp.isfinite(c_cert), axis=1, dtype=jnp.int32)
    else:
        n_off = n_cand
    occ_pre = jnp.sum(jnp.isfinite(queue.cert), axis=1, dtype=jnp.int32) + n_off
    occ_after = jnp.sum(jnp.isfinite(new.cert), axis=1, dtype=jnp.int32)
    return (
        new,
        jnp.sum(n_cand, dtype=jnp.int32),
        jnp.sum(occ_pre - occ_after, dtype=jnp.int32),
        jnp.max(occ_pre),
        n_dropped,
        n_rejected,
    )


def _dense_push_candidates(
    inflight: jnp.ndarray,
    cand_cert: jnp.ndarray,
    cand_ids: jnp.ndarray,
    alive: jnp.ndarray,
    local_gids: jnp.ndarray,
    delay_rows: jnp.ndarray,
    r: jnp.ndarray | None = None,
    dst_cert: jnp.ndarray | None = None,
    fault: FaultPlan | None = None,
    pod_of=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse-control push into the dense ``(W_local, W, D)`` in-flight
    buffer (``inflight_capacity == 0``): scatter each candidate's
    certificate at ``[dst, src, delay-1]`` by global id — O(W_local·m)
    scatter work instead of the O(W_local·W·D) dense push mask. Invalid
    candidates scatter to the OOB source index W and drop. With a
    ``fault`` plan, dropped/rejected candidates also go OOB (duplication
    is a no-op on the dense buffer — the same cell written twice — and
    reorder is rejected at construction). Returns ``(inflight, n_pushed,
    n_dropped, n_rejected)``."""
    w = delay_rows.shape[1]
    wl, m = delay_rows.shape[0], cand_ids.shape[0]
    ids_c = jnp.clip(cand_ids, 0, w - 1).astype(jnp.int32)
    val = _candidate_valid(cand_cert, cand_ids, alive, local_gids, w)
    cert2 = jnp.where(val, cand_cert[None, :], jnp.inf)  # (wl, m) per-edge
    n_dropped = jnp.zeros((), jnp.int32)
    n_rejected = jnp.zeros((), jnp.int32)
    if fault is not None:
        src2 = jnp.broadcast_to(ids_c[None, :], (wl, m))
        cert2, _, _, n_dropped, n_rejected = _inject_faults(
            fault, pod_of, r, local_gids, src2, cert2, None, dst_cert, depth=0
        )
        val = val & jnp.isfinite(cert2)
    ids2 = jnp.where(val, cand_ids[None, :], w)  # OOB -> dropped
    d = jnp.take_along_axis(delay_rows, jnp.broadcast_to(ids_c[None, :], (wl, m)), axis=1)
    row_idx = jnp.broadcast_to(jnp.arange(wl, dtype=jnp.int32)[:, None], (wl, m))
    inflight = inflight.at[row_idx, ids2, d - 1].set(cert2, mode="drop")
    return inflight, jnp.sum(val, dtype=jnp.int32), n_dropped, n_rejected


class EngineState(NamedTuple):
    worker: Any
    certs: jnp.ndarray  # (W,) f32 — post-round certificates, carried so
    # the next round's acceptance test needs no third certificates() call
    alive: jnp.ndarray  # (W,) bool
    credit: jnp.ndarray  # (W,) f32 compute credit (laggard model)
    clock: jnp.ndarray  # (W,) f32 per-worker simulated seconds
    #: dense mode: (W, W, D) f32 — [dst, src, d] certs, +inf = empty.
    #: sparse mode (inflight_capacity > 0): a :class:`PendingQueue`
    inflight: Any
    ring: Any  # model snapshots, leading (D, W) — (n_pods*D, W) on a pod mesh
    round: jnp.ndarray  # () i32
    sent: jnp.ndarray  # () i32
    accepted: jnp.ndarray  # () i32
    discarded: jnp.ndarray  # () i32
    cost_total: jnp.ndarray  # () f32
    #: (W,) bool — cross-pod tier: workers whose improvement is pending
    #: the next pod-axis flush (constant False off the pod-mesh engine)
    xpend: jnp.ndarray
    #: () i32 — pushes that crossed a pod boundary (DCN tier); a
    #: (n_dev,) per-shard partial on the sharded engines, like `sent`
    sent_dcn: jnp.ndarray
    #: () i32 — sparse-mode candidates offered but not retained
    #: (capacity evictions); constant 0 in dense mode and, like `sent`,
    #: a (n_dev,) per-shard partial on the sharded engines
    evicted: jnp.ndarray
    #: () i32 — peak pre-eviction pending-queue occupancy seen by any
    #: destination (a measured lower bound on the capacity that makes
    #: the run exact); (n_dev,) per-shard partials when sharded
    occ_peak: jnp.ndarray
    #: () i32 — messages dropped by FaultPlan injection (random drop
    #: plus partition-window drops); (n_dev,) partials when sharded
    dropped_inj: jnp.ndarray
    #: () i32 — candidates rejected by the eps-gate soundness check
    #: (non-finite or non-improving certs, active only under a
    #: FaultPlan); (n_dev,) partials when sharded
    corrupt_rej: jnp.ndarray


class RoundInfo(NamedTuple):
    """Small per-round summary fetched to the host for history/stop."""

    certs: jnp.ndarray  # (W,)
    changed: jnp.ndarray  # (W,) bool — cert changed this round (fire or adopt)
    clock: jnp.ndarray  # (W,)
    alive: jnp.ndarray  # (W,)


def _to_host(x: Any) -> np.ndarray:
    """Blocking device-to-host read inside :meth:`TMSNEngine.run`,
    counted as the run's ``host_fetches``."""
    telemetry.count("host_fetches")
    return np.asarray(x)


def _tree_stack_rows(tree: Any, depth: int) -> Any:
    """Tile a stacked (W, ...) pytree into a (D, W, ...) ring."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (depth,) + a.shape).copy(), tree
    )


def _compile(jitted, *args):
    """Compile ``jitted`` for ``args`` ahead of time and register the
    scope map of the very program that runs (the instruction names of a
    profiler trace are this executable's)."""
    compiled = jitted.lower(*args).compile()
    telemetry.register_program(compiled.as_text())
    return compiled


class TMSNEngine:
    """Round-based TMSN run over a batched worker."""

    def __init__(self, worker: BatchedTMSNWorker, config: EngineConfig) -> None:
        self.worker = worker
        self.config = config
        w = config.n_workers

        if config.gossip_mode not in ("dense", "gated"):
            raise ValueError(
                f"gossip_mode must be 'dense' or 'gated', got {config.gossip_mode!r}"
            )
        if config.gossip_top_k < 1:
            raise ValueError(f"gossip_top_k must be >= 1, got {config.gossip_top_k}")
        if config.rounds_per_dispatch < 1:
            raise ValueError(
                f"rounds_per_dispatch must be >= 1, got {config.rounds_per_dispatch}"
            )
        if config.cross_pod_every_k < 1:
            raise ValueError(
                f"cross_pod_every_k must be >= 1, got {config.cross_pod_every_k}"
            )
        if config.cross_pod_top_k < 1:
            raise ValueError(
                f"cross_pod_top_k must be >= 1, got {config.cross_pod_top_k}"
            )
        if isinstance(config.inflight_capacity, str):
            if config.inflight_capacity != "auto":
                raise ValueError(
                    f"inflight_capacity must be an int >= 0 or 'auto', "
                    f"got {config.inflight_capacity!r}"
                )
        elif config.inflight_capacity < 0:
            raise ValueError(
                f"inflight_capacity must be >= 0, got {config.inflight_capacity}"
            )
        if config.round_step_impl not in ("pallas", "ref"):
            raise ValueError(
                f"round_step_impl must be 'pallas' or 'ref', got {config.round_step_impl!r}"
            )
        if config.control_plane not in ("dense", "sparse"):
            raise ValueError(
                f"control_plane must be 'dense' or 'sparse', got {config.control_plane!r}"
            )
        if config.publish_every_k < 0:
            raise ValueError(
                f"publish_every_k must be >= 0, got {config.publish_every_k}"
            )
        if not config.publish_eps >= 0.0:  # also rejects NaN
            raise ValueError(f"publish_eps must be >= 0, got {config.publish_eps}")
        #: serving-tier publisher (an AdoptionSlot-shaped object); None
        #: until attach_publisher() — the clean run() path stays free of
        #: the per-chunk certificate fetch
        self._publisher: Any = None
        self._published_cert = float("inf")
        self._next_publish_round = 0
        self._control_sparse = config.control_plane == "sparse"
        #: 0 = dense (W, W, D) oracle; C >= 1 = bounded PendingQueue;
        #: None = "auto", resolved by a warm-up probe at run() time
        self._capacity: int | None = (
            None
            if config.inflight_capacity == "auto"
            else int(config.inflight_capacity)
        )
        #: capacity the auto probe selected (0 when capacity is explicit)
        self._auto_selected = 0

        delay = np.asarray(config.delay_rounds)
        if delay.ndim == 0:
            delay = np.full((w, w), int(delay))
        if delay.shape != (w, w):
            raise ValueError(f"delay_rounds must be scalar or ({w},{w}), got {delay.shape}")
        self._delay = jnp.asarray(np.maximum(delay, 1), jnp.int32)
        self._depth = int(np.maximum(delay, 1).max())

        speed = np.ones(w) if config.speed is None else np.asarray(config.speed, np.float64)
        if speed.shape != (w,):
            raise ValueError(f"speed must be ({w},), got {speed.shape}")
        self._speed = jnp.asarray(speed, jnp.float32)
        self._speed_norm = jnp.asarray(speed / speed.max(), jnp.float32)

        fail = (
            np.full(w, np.iinfo(np.int32).max)
            if config.fail_round is None
            else np.asarray(config.fail_round).copy()
        )
        if fail.shape != (w,):
            raise ValueError(f"fail_round must be ({w},), got {fail.shape}")

        # --- elastic membership: spares, joins, leaves ---------------------
        spares = int(config.spare_slots)
        if not 0 <= spares < w:
            raise ValueError(
                f"spare_slots must be in [0, n_workers), got {spares} (n_workers={w})"
            )
        never = np.iinfo(np.int32).max
        join_round = np.zeros(w, np.int64)
        if spares:
            join_round[w - spares :] = never  # spares without a join stay masked
        plan = config.membership
        if plan is not None:
            if not isinstance(plan, MembershipPlan):
                raise ValueError(
                    f"membership must be a MembershipPlan, got {type(plan).__name__}"
                )
            seen_slots: set[int] = set()
            for k, slot in plan.joins:
                k, slot = int(k), int(slot)
                if k < 1:
                    raise ValueError(f"membership join rounds are 1-based, got {k}")
                if not w - spares <= slot < w:
                    raise ValueError(
                        f"membership join slot {slot} is not a spare "
                        f"(spare region is [{w - spares}, {w}), "
                        f"spare_slots={spares})"
                    )
                if slot in seen_slots:
                    raise ValueError(f"membership joins slot {slot} twice")
                seen_slots.add(slot)
                join_round[slot] = k - 1  # 1-based: k=1 == alive from round 0
            for k, leaver in plan.leaves:
                k, leaver = int(k), int(leaver)
                if k < 1:
                    raise ValueError(f"membership leave rounds must be >= 1, got {k}")
                if not 0 <= leaver < w:
                    raise ValueError(
                        f"membership leave worker {leaver} out of range [0, {w})"
                    )
                fail[leaver] = min(int(fail[leaver]), k)
        self._join_round_np = join_round
        self._join_round = jnp.asarray(join_round, jnp.int32)
        #: joins/spares change the alive/credit dataflow; keep the clean
        #: engine's exact graph when the feature is off
        self._has_joins = spares > 0 or (plan is not None and bool(plan.joins))
        self._fail_round = jnp.asarray(fail, jnp.int32)

        # --- fault injection -----------------------------------------------
        fplan = config.fault_plan
        if fplan is None:
            fplan = _parse_fault_spec(config.fault_spec)
        elif not isinstance(fplan, FaultPlan):
            raise ValueError(
                f"fault_plan must be a FaultPlan, got {type(fplan).__name__}"
            )
        if fplan is not None:
            for fname in ("drop_prob", "duplicate_prob", "corrupt_prob"):
                p = getattr(fplan, fname)
                if not 0.0 <= p <= 1.0:
                    raise ValueError(f"FaultPlan.{fname} must be in [0, 1], got {p}")
            if fplan.reorder_max < 0:
                raise ValueError(
                    f"FaultPlan.reorder_max must be >= 0, got {fplan.reorder_max}"
                )
            if fplan.reorder_max > 0 and self._capacity == 0:
                raise ValueError(
                    "FaultPlan.reorder_max > 0 needs the pending-queue in-flight "
                    "state (inflight_capacity >= 1 or 'auto'): the dense (W, W, D) "
                    "buffer derives ring slots from the static delay matrix, so a "
                    "jittered delivery would fetch a wrong-generation payload"
                )
            if not fplan.active:
                fplan = None  # all-zero plan == clean semantics, same graph
        self._fault: FaultPlan | None = fplan
        #: (W,) pod index per global worker id on the pod-mesh engine
        #: (set by the sharded subclass); None = no pod geometry, which
        #: makes the FaultPlan partition window inert
        self._pod_of = None

        #: compiled chunk dispatchers keyed by scan length (the main
        #: chunk size plus at most one remainder length per run)
        self._chunks: dict[int, Any] = {}

        #: workers without a sampling phase omit the resample hooks and
        #: the round step statically drops the whole resample branch
        self._has_resample = has_resample_hooks(worker)
        #: traffic-accounting payload size: the worker's own
        #: payload_bytes() when defined, else derived from the exported
        #: model pytree via jax.eval_shape (cannot drift from reality)
        self._payload_bytes = resolve_payload_bytes(worker, w, config.seed)
        #: the worker's shared read-only arrays, a step argument
        self._shared = shared_data(worker)

    # ------------------------------------------------------------------
    # dispatch chunking: K rounds per jitted call
    # ------------------------------------------------------------------
    def _chunk_rounds(self, step, any_reduce, state: EngineState, length: int):
        """Run ``length`` rounds of ``step``; returns the final state and
        the :class:`RoundInfo` of each round, stacked over ``length``.

        ``step`` is the (possibly shard-mapped) single-round step;
        ``any_reduce`` turns a (local) boolean vector into a scalar
        "any worker, any shard" — ``jnp.any`` on one device, a psum on
        the sharded engine. When ``target_certificate`` is set, the
        chunk stops on the crossing round so the final state is
        identical to an unchunked run for every chunk size.
        """
        target = self.config.target_certificate

        # A loop that exits on the crossing round, not a cond per round
        # that either runs the step or passes the state through: such a
        # cond makes XLA copy every state leaf the step writes (the
        # worker's sample included) in every round. `done` derives from
        # an all-shard reduction, so every device runs the same rounds
        # and the collectives inside stay uniform.
        def frozen_info(st):
            # the rounds after the crossing report the state as it stands
            # and no changes (so host history/stop logic sees the crossing
            # round as the last live one)
            return RoundInfo(
                certs=st.certs,
                changed=jnp.zeros_like(st.alive),
                clock=st.clock,
                alive=st.alive,
            )

        def body(carry):
            k, st, _, infos = carry
            st, info = step(st)
            infos = jax.tree_util.tree_map(lambda a, v: a.at[k].set(v), infos, info)
            if target is None:
                return k + 1, st, jnp.zeros((), bool), infos
            return k + 1, st, any_reduce(info.alive & (info.certs <= target)), infos

        infos = jax.tree_util.tree_map(
            lambda a: jnp.zeros((length,) + a.shape, a.dtype), frozen_info(state)
        )
        carry = (jnp.zeros((), jnp.int32), state, jnp.zeros((), bool), infos)
        ran, state, _, infos = jax.lax.while_loop(
            lambda c: (c[0] < length) & ~c[2], body, carry
        )
        if target is None:
            return state, infos
        with jax.named_scope(telemetry.FREEZE):
            live = jnp.arange(length) < ran
            infos = jax.tree_util.tree_map(
                lambda a, f: jnp.where(live.reshape((length,) + (1,) * f.ndim), a, f[None]),
                infos,
                frozen_info(state),
            )
        return state, infos

    def _build_chunk(self, length: int, state: EngineState):
        """Compiled ``state -> (state, RoundInfo stacked over length)``
        for states shaped like ``state``; the sharded engine overrides
        this to run the rounds inside ``shard_map``. The worker's shared
        read-only data enters as an argument, so it is never baked into
        the program as a constant."""
        def chunk(state: EngineState, shared: Any):
            with bind_shared_data(self.worker, shared):
                return self._chunk_rounds(self._round_step, jnp.any, state, length)

        step = _compile(jax.jit(chunk), state, self._shared)
        return lambda state: step(state, self._shared)

    def _chunk_fn(self, length: int, state: EngineState):
        """The chunk program of ``length`` rounds, compiled ahead of time
        on a miss (counted as the run's ``chunk_compiles``)."""
        fn = self._chunks.get(length)
        if fn is None:
            telemetry.count("chunk_compiles")
            fn = self._chunks[length] = self._build_chunk(length, state)
        return fn

    # ------------------------------------------------------------------
    def _init_state(self) -> EngineState:
        cfg = self.config
        w, d = cfg.n_workers, self._depth
        wstate = self.worker.init_batch(w, cfg.seed)
        models = self.worker.export_models(wstate)
        if self._capacity:
            inflight = _empty_queue(w, self._capacity)
        else:
            inflight = jnp.full((w, w, d), jnp.inf, jnp.float32)
        if self._has_joins:
            alive0 = jnp.asarray(self._join_round_np <= 0)
        else:
            alive0 = jnp.ones((w,), bool)
        return EngineState(
            worker=wstate,
            certs=jnp.asarray(self.worker.certificates(wstate), jnp.float32),
            alive=alive0,
            credit=jnp.zeros((w,), jnp.float32),
            clock=jnp.zeros((w,), jnp.float32),
            inflight=inflight,
            ring=_tree_stack_rows(models, d),
            round=jnp.zeros((), jnp.int32),
            sent=jnp.zeros((), jnp.int32),
            accepted=jnp.zeros((), jnp.int32),
            discarded=jnp.zeros((), jnp.int32),
            cost_total=jnp.zeros((), jnp.float32),
            xpend=jnp.zeros((w,), bool),
            sent_dcn=jnp.zeros((), jnp.int32),
            evicted=jnp.zeros((), jnp.int32),
            occ_peak=jnp.zeros((), jnp.int32),
            dropped_inj=jnp.zeros((), jnp.int32),
            corrupt_rej=jnp.zeros((), jnp.int32),
        )

    def _deliver_sparse(
        self,
        queue: PendingQueue,
        certs0: jnp.ndarray,
        alive: jnp.ndarray,
        credit: jnp.ndarray,
        speed_norm: jnp.ndarray,
        r: jnp.ndarray,
    ):
        """Fused sparse delivery: argmin over this round's due entries
        (ties to the lowest source id, matching the dense argmin),
        eps-gated accept, arrival clearing, and the laggard-credit
        update — one kernel call (``round_step_impl`` picks the Pallas
        kernel or the jnp reference; both are bit-identical).

        Returns ``(queue', best_cert, best_src, best_slot, take,
        n_arrivals, credit', active)``; the imports are deferred so
        ``repro.core.engine`` never pulls the kernels package (and its
        worker-side dependencies) at module import time.
        """
        if self.config.round_step_impl == "ref":
            from repro.kernels.ref import round_step_ref as deliver
        else:
            from repro.kernels.ops import round_deliver as deliver
        q_cert, best_cert, best_src, best_slot, take, n_arr, credit2, active = deliver(
            queue.cert,
            queue.due,
            queue.src,
            queue.slot,
            certs0,
            alive,
            credit,
            speed_norm,
            r,
            eps=float(self.config.eps),
        )
        return (
            queue._replace(cert=q_cert),
            best_cert,
            best_src,
            best_slot,
            take,
            jnp.sum(n_arr, dtype=jnp.int32),
            credit2,
            active,
        )

    def _top_k_candidates(self, mask, certs, k: int):
        """Rows of the (locally) best k candidates under ``mask`` and a
        validity flag per row. Stable argsort: ties pick the lowest
        worker row, matching the delivery argmin's tie-break. Shared by
        gated payload gossip, the cross-pod flush, and the sparse
        control plane."""
        score = jnp.where(mask, certs, jnp.inf)
        rows = jnp.argsort(score, stable=True)[:k]
        return rows, jnp.isfinite(score[rows])

    def _round_step(self, state: EngineState) -> tuple[EngineState, RoundInfo]:
        cfg = self.config
        w, depth = cfg.n_workers, self._depth
        r = state.round
        dst_idx = jnp.arange(w)
        if self._has_joins:
            # joins are sticky (state.alive | ...) and compose with
            # fail-stop; a joiner's laggard credit is reseeded on its
            # join round (the accumulator accrued while it was masked).
            # Its model/PRNG rows were never touched while masked
            # (worker contract), so its batch stream is the untouched
            # init_batch stream — no recompilation, no state surgery.
            alive = (state.alive | (r >= self._join_round)) & (r < self._fail_round)
            credit_in = jnp.where(r == self._join_round, 0.0, state.credit)
        else:
            alive = state.alive & (r < self._fail_round)
            credit_in = state.credit

        # last round's post-scan certificates, carried in the state (no
        # third certificates() call per round)
        certs0 = state.certs

        # --- 1.+2.(+3. credit) deliver arrivals due this round ------------
        with jax.named_scope(telemetry.DELIVER):
            if self._capacity:
                # sparse path: delivery argmin + accept gate + credit are
                # one fused kernel call; clearing the delivered certs
                # replaces the dense buffer shift (dues are absolute)
                (
                    inflight,
                    best_cert,
                    best_src,
                    sent_slot,
                    take,
                    n_arrivals,
                    credit,
                    active,
                ) = self._deliver_sparse(
                    state.inflight, certs0, alive, credit_in, self._speed_norm, r
                )
            else:
                arr = state.inflight[:, :, 0]  # (dst, src) certs
                arr_live = jnp.where(alive[:, None], arr, jnp.inf)
                best_src = jnp.argmin(arr_live, axis=1)  # (W,)
                best_cert = arr_live[dst_idx, best_src]
                take = accepts(certs0, best_cert, cfg.eps) & jnp.isfinite(best_cert)
                n_arrivals = jnp.sum(jnp.isfinite(arr), dtype=jnp.int32)
                sent_slot = (r - self._delay[best_src, dst_idx]) % depth
                # shift the in-flight buffer
                inflight = jnp.concatenate(
                    [state.inflight[:, :, 1:], jnp.full((w, w, 1), jnp.inf, jnp.float32)],
                    axis=2,
                )
                credit = credit_in + self._speed_norm
                active = alive & (credit >= 1.0 - 1e-6)
                credit = jnp.where(active, credit - 1.0, credit)
        n_taken = jnp.sum(take, dtype=jnp.int32)

        with jax.named_scope(telemetry.ADOPT):
            in_models = jax.tree_util.tree_map(
                lambda a: a[sent_slot, best_src], state.ring
            )

            def _adopt(operand):
                wstate, models, c, t = operand
                return self.worker.adopt_batch(wstate, models, c, t)

            wstate, adopt_cost = jax.lax.cond(
                jnp.any(take),
                _adopt,
                lambda operand: (operand[0], jnp.zeros((w,), jnp.float32)),
                (state.worker, in_models, best_cert, take),
            )

        # --- 3. one segment per live, credit-covered worker ---------------
        # (workers without the optional resample hooks skip this branch
        # statically — see repro.core.worker.has_resample_hooks)
        with jax.named_scope(telemetry.RESAMPLE):
            if self._has_resample:
                need = self.worker.needs_resample(wstate) & active
                # no guard: the hook runs no work for workers not in
                # `need`, and a cond would copy the state it carries
                wstate, resample_cost = self.worker.resample_round(wstate, need)
                scan_mask = active & ~need
            else:
                resample_cost = jnp.zeros((w,), jnp.float32)
                scan_mask = active
        with jax.named_scope(telemetry.SCAN):
            certs_pre = self.worker.certificates(wstate)
            wstate, scan_cost, fired = self.worker.scan_round(wstate, scan_mask)
            certs = self.worker.certificates(wstate)

        cost = adopt_cost + resample_cost + scan_cost
        clock = state.clock + cost / jnp.maximum(self._speed, 1e-12)

        # --- 4. broadcast strict improvements -----------------------------
        # (eps gates acceptance only — see the note in simulator.run)
        with jax.named_scope(telemetry.BROADCAST):
            improved = fired & improves(certs_pre, certs, 0.0) & scan_mask
            n_evicted = jnp.zeros((), jnp.int32)
            occ_pre_max = jnp.zeros((), jnp.int32)
            n_dropped = jnp.zeros((), jnp.int32)
            n_rejected = jnp.zeros((), jnp.int32)
            if self._control_sparse:
                # sparse control plane: only the top-k improvers are offered
                # (single-device analogue of the (n_dev, k) all_gather). The
                # suppressed runner-ups could never have been accepted under
                # uniform delay — every receiver's best arrival is the
                # global min, except the min's own sender, whose local cert
                # is already at least as good as any runner-up.
                kc = min(int(cfg.gossip_top_k), w)
                rows, validk = self._top_k_candidates(improved, certs, kc)
                cand_ids = jnp.where(validk, rows.astype(jnp.int32), w)
                cand_certs = jnp.where(validk, certs[rows], jnp.inf)
                if self._capacity:
                    (
                        inflight,
                        n_pushed,
                        n_evicted,
                        occ_pre_max,
                        n_dropped,
                        n_rejected,
                    ) = _queue_push_candidates(
                        inflight,
                        cand_certs,
                        cand_ids,
                        alive,
                        dst_idx.astype(jnp.int32),
                        self._delay.T,  # (dst, src) rows
                        r,
                        depth,
                        cfg.round_step_impl,
                        dst_cert=certs,
                        fault=self._fault,
                        pod_of=self._pod_of,
                    )
                else:
                    inflight, n_pushed, n_dropped, n_rejected = _dense_push_candidates(
                        inflight,
                        cand_certs,
                        cand_ids,
                        alive,
                        dst_idx.astype(jnp.int32),
                        self._delay.T,
                        r=r,
                        dst_cert=certs,
                        fault=self._fault,
                        pod_of=self._pod_of,
                    )
            elif self._capacity:
                (
                    inflight,
                    n_pushed,
                    n_evicted,
                    occ_pre_max,
                    n_dropped,
                    n_rejected,
                ) = _queue_push(
                    inflight,
                    jnp.where(improved, certs, jnp.inf),
                    alive,
                    dst_idx,
                    self._delay.T,  # (dst, src) rows
                    r,
                    depth,
                    dst_cert=certs,
                    fault=self._fault,
                    pod_of=self._pod_of,
                )
            elif self._fault is None:
                d_idx = jnp.arange(depth)[None, None, :]
                # push_mask[dst, src, d] — delay is indexed [src, dst]
                push_mask = (
                    improved[None, :, None]
                    & alive[:, None, None]
                    & (dst_idx[:, None] != dst_idx[None, :])[:, :, None]
                    & (d_idx == (self._delay.T[:, :, None] - 1))
                )
                inflight = jnp.where(push_mask, certs[None, :, None], inflight)
                n_pushed = jnp.sum(push_mask, dtype=jnp.int32)
            else:
                # faulted dense push: same mask, but carried as a per-edge
                # (dst, src) certificate matrix so _inject_faults can drop /
                # corrupt / soundness-reject individual edges
                push2 = (
                    improved[None, :]
                    & alive[:, None]
                    & (dst_idx[:, None] != dst_idx[None, :])
                )
                cert_mat = jnp.where(push2, certs[None, :], jnp.inf)
                src_mat = jnp.broadcast_to(
                    dst_idx[None, :].astype(jnp.int32), (w, w)
                )
                cert_mat, _, _, n_dropped, n_rejected = _inject_faults(
                    self._fault,
                    self._pod_of,
                    r,
                    dst_idx.astype(jnp.int32),
                    src_mat,
                    cert_mat,
                    None,
                    certs,
                    depth,
                )
                d_idx = jnp.arange(depth)[None, None, :]
                push_mask = jnp.isfinite(cert_mat)[:, :, None] & (
                    d_idx == (self._delay.T[:, :, None] - 1)
                )
                inflight = jnp.where(push_mask, cert_mat[:, :, None], inflight)
                n_pushed = jnp.sum(push2, dtype=jnp.int32)  # logical sends

            # --- 5. snapshot the models into the ring -------------------------
            # gated to broadcasters: ring[slot, src] is only ever read for a
            # message src pushed at that slot's round, so non-improved
            # workers keep their (dead) old entry instead of paying a write
            models = self.worker.export_models(wstate)
            ring = jax.tree_util.tree_map(
                lambda buf, m: buf.at[r % depth].set(
                    jnp.where(
                        improved.reshape((-1,) + (1,) * (m.ndim - 1)), m, buf[r % depth]
                    )
                ),
                state.ring,
                models,
            )

        new_state = EngineState(
            worker=wstate,
            certs=certs,
            alive=alive,
            credit=credit,
            clock=clock,
            inflight=inflight,
            ring=ring,
            round=r + 1,
            sent=state.sent + n_pushed,
            accepted=state.accepted + n_taken,
            discarded=state.discarded + (n_arrivals - n_taken),
            cost_total=state.cost_total + jnp.sum(cost),
            xpend=state.xpend,
            sent_dcn=state.sent_dcn,
            evicted=state.evicted + n_evicted,
            occ_peak=jnp.maximum(state.occ_peak, occ_pre_max),
            dropped_inj=state.dropped_inj + n_dropped,
            corrupt_rej=state.corrupt_rej + n_rejected,
        )
        info = RoundInfo(
            certs=certs, changed=take | improved, clock=clock, alive=alive
        )
        return new_state, info

    # ------------------------------------------------------------------
    def _resolve_auto_capacity(self) -> None:
        """Resolve ``inflight_capacity="auto"``: run a short warm-up
        probe at an explicit capacity, doubling until nothing is evicted
        (so the measured ``inflight_occupancy_peak`` is the true
        unbounded peak, not a capacity-truncated one), then size the
        real run's queues at peak × :data:`AUTO_CAPACITY_HEADROOM`. The
        probe inherits every protocol knob (same engine class, same
        mesh), so its occupancy is the run's own warm-up occupancy."""
        cfg = self.config
        w = cfg.n_workers
        warmup = min(max(2 * self._depth + 2, 8), cfg.max_rounds)
        hard_max = w * self._depth  # every (src, pending-round) pair
        probe_cap = min(max(64, 2 * self._depth), hard_max)
        while True:
            probe_cfg = dataclasses.replace(
                cfg,
                inflight_capacity=int(probe_cap),
                max_rounds=warmup,
                target_certificate=None,
                record_history=False,
            )
            probe = make_engine(self.worker, probe_cfg)
            res = probe.run()
            if res.messages_evicted == 0 or probe_cap >= hard_max:
                break
            probe_cap = min(2 * probe_cap, hard_max)
        peak = max(int(res.inflight_occupancy_peak), 0)
        self._capacity = max(1, math.ceil(peak * AUTO_CAPACITY_HEADROOM))
        self._auto_selected = self._capacity

    def attach_publisher(self, slot: Any) -> None:
        """Register a snapshot publisher (anything with a
        ``publish(params, cert, round)`` method — canonically a
        :class:`repro.launch.serving.AdoptionSlot`). At the first chunk
        boundary at/after every ``publish_every_k``-th round, :meth:`run`
        exports the best-certificate worker's model and publishes it when
        the certificate improved by more than ``publish_eps`` since the
        last publish. Host-side only: the jitted round step is untouched,
        and :class:`~repro.core.engine_sharded.ShardedTMSNEngine` inherits
        the hook unchanged (chunk outputs are global arrays)."""
        if self.config.publish_every_k < 1:
            raise ValueError(
                "attach_publisher requires publish_every_k >= 1 "
                f"(got {self.config.publish_every_k}); set it in EngineConfig "
                "or via REPRO_PUBLISH_EVERY_K"
            )
        self._publisher = slot

    def _maybe_publish(self, state: EngineState, rounds: int, final: bool = False) -> None:
        """Publish the best-certificate model if due and improved."""
        if self._publisher is None:
            return
        if not final and rounds < self._next_publish_round:
            return
        k = int(self.config.publish_every_k)
        while self._next_publish_round <= rounds:
            self._next_publish_round += k
        live = np.where(_to_host(state.alive), _to_host(state.certs), np.inf)
        best = int(np.argmin(live))
        best_cert = float(live[best])
        if not np.isfinite(best_cert):
            return
        if best_cert >= self._published_cert - float(self.config.publish_eps):
            return
        models = self.worker.export_models(state.worker)
        params = jax.tree_util.tree_map(lambda a: _to_host(a[best]), models)
        self._publisher.publish(params, cert=best_cert, round=rounds)
        self._published_cert = best_cert

    def run(self) -> SimResult:
        """One whole run from a fresh initial state, recorded as a run of
        :mod:`repro.core.telemetry`: the spans ``tmsn.init``,
        ``tmsn.dispatch``, ``tmsn.fetch``, ``tmsn.host`` and
        ``tmsn.finalize`` under ``tmsn.run``, and the counters
        ``chunks``, ``rounds``, ``host_fetches``, ``chunk_compiles``,
        ``adoptions`` and, for a worker state with a ``resamples`` field,
        ``resamples``."""
        with telemetry.run_scope():
            return self._run()

    def _run(self) -> SimResult:
        cfg = self.config
        if self._capacity is None:
            self._resolve_auto_capacity()
        # each run() publishes from scratch: the first due boundary with
        # a finite best certificate publishes unconditionally
        self._published_cert = float("inf")
        self._next_publish_round = max(int(cfg.publish_every_k), 1)
        with telemetry.span(telemetry.INIT):
            state = self._init_state()
            certs0 = _to_host(state.certs)
        history: list[tuple[float, int, float]] = [
            (0.0, i, float(certs0[i])) for i in range(cfg.n_workers)
        ]

        rounds = 0
        # only fetch per-chunk info to the host when something consumes
        # it — a fixed-round throughput run stays free of device syncs
        # so JAX can queue whole chunks asynchronously
        fetch = cfg.record_history or cfg.target_certificate is not None
        k = int(cfg.rounds_per_dispatch)  # validated >= 1 in __init__
        remaining = int(cfg.max_rounds)
        while remaining > 0:
            kk = min(k, remaining)
            with telemetry.span(telemetry.DISPATCH):
                state, infos = self._chunk_fn(kk, state)(state)
            telemetry.count("chunks")
            remaining -= kk
            if not fetch:
                with telemetry.span(telemetry.HOST):
                    rounds += kk
                    self._maybe_publish(state, rounds)
                continue
            with telemetry.span(telemetry.FETCH):
                certs_k = _to_host(infos.certs)  # (kk, W)
                if cfg.target_certificate is not None:
                    alive_k = _to_host(infos.alive)
                if cfg.record_history:
                    changed_k = _to_host(infos.changed)
                    clock_k = _to_host(infos.clock)
            with telemetry.span(telemetry.HOST):
                stop = None
                if cfg.target_certificate is not None:
                    # f32 target, matching the in-scan freeze comparison —
                    # a float64 host compare could disagree with the device
                    # in the ULP window around a non-f32-representable target
                    hit = np.any(
                        (certs_k <= np.float32(cfg.target_certificate)) & alive_k,
                        axis=1,
                    )
                    if hit.any():
                        stop = int(np.argmax(hit))
                last = kk - 1 if stop is None else stop
                rounds += last + 1
                if cfg.record_history:
                    # bulk append over the stacked chunk: row-major nonzero
                    # keeps (round, worker) order identical to the old
                    # per-round per-worker Python loop
                    rr, ww = np.nonzero(changed_k[: last + 1])
                    history.extend(
                        zip(clock_k[rr, ww].tolist(), ww.tolist(), certs_k[rr, ww].tolist())
                    )
                self._maybe_publish(state, rounds)
            if stop is not None:
                break
        telemetry.count("rounds", rounds)
        with telemetry.span(telemetry.FINALIZE):
            return self._finalize(state, history, rounds)

    def _finalize(self, state: EngineState, history: list, rounds: int) -> SimResult:
        cfg = self.config
        # final flush: a last-chunk improvement between cadence points
        # still reaches the serving tier before run() returns
        self._maybe_publish(state, rounds, final=True)

        certs = _to_host(state.certs)
        models = self.worker.export_models(state.worker)
        # counters are () scalars on the single-device engine and
        # (n_devices,) per-shard partials on the sharded one; np.sum
        # covers both (the per-shard reduction happens here, once)
        ictrl, dctrl = self._control_split()
        traffic = TrafficCounters.from_shards(
            sent=_to_host(state.sent),
            accepted=_to_host(state.accepted),
            discarded=_to_host(state.discarded),
            payload_bytes=self._payload_bytes,
            sent_dcn=_to_host(state.sent_dcn),
            evicted=_to_host(state.evicted),
            control_bytes=(ictrl + dctrl) * rounds,
            dropped_injected=_to_host(state.dropped_inj),
            corrupt_rejected=_to_host(state.corrupt_rej),
        )
        telemetry.count("adoptions", traffic.accepted)
        resamples = getattr(state.worker, "resamples", None) if self._has_resample else None
        if resamples is not None:
            telemetry.count("resamples", int(np.sum(_to_host(resamples))))
        # a join "happened" when its spare went live strictly after
        # round 0 and before the run ended (k=1 joins are full members
        # from the start, so a k=1 run reports 0 — matching the plain
        # run it is bit-identical to)
        jr = self._join_round_np
        workers_joined = int(np.sum((jr > 0) & (jr < rounds)))
        final_models = [
            jax.tree_util.tree_map(lambda a, i=i: a[i], models)
            for i in range(cfg.n_workers)
        ]
        ici_bytes, dcn_bytes = self._gossip_split()
        return SimResult.from_traffic(
            traffic,
            history=history,
            final_certificates=[float(c) for c in certs],
            final_models=final_models,
            sim_time=float(_to_host(state.clock).max()),
            cost_units_total=float(np.sum(_to_host(state.cost_total))),
            events_processed=rounds * cfg.n_workers,
            rounds=rounds,
            gossip_bytes_per_round=ici_bytes + dcn_bytes,
            gossip_bytes_per_round_ici=ici_bytes,
            gossip_bytes_per_round_dcn=dcn_bytes,
            gossip_mode=self._gossip_mode(),
            inflight_occupancy_peak=int(np.max(_to_host(state.occ_peak))),
            control_bytes_per_round=ictrl + dctrl,
            control_plane=cfg.control_plane,
            inflight_capacity_selected=self._auto_selected,
            workers_joined=workers_joined,
        )

    def _gossip_split(self) -> tuple[int, int]:
        """(ICI, DCN) cross-device exchange footprint per round; the DCN
        leg is amortized over ``cross_pod_every_k``. (0, 0) on one
        device."""
        return 0, 0

    def _control_split(self) -> tuple[int, int]:
        """(ICI, DCN) CONTROL-plane sub-footprint of
        :meth:`_gossip_split` per round — the certificate/flag/id bytes
        as opposed to model payload bytes. (0, 0) on one device."""
        return 0, 0

    def _gossip_mode(self) -> str:
        """Mode label for SimResult; one device has no cross-device
        gossip, so the config knob is reported as inert."""
        return "dense"


def quantize_latency(
    base_latency: float,
    jitter: float,
    round_dt: float,
    n_workers: int,
    seed: int = 0,
) -> np.ndarray:
    """Quantize the simulator's continuous per-link latency model to an
    integer (W, W) round-delay matrix: ``delay = max(1, round(lat/dt))``.

    Jitter is drawn from the same U[0, jitter) distribution as the event
    sim, but sampled ONCE per link and frozen for the whole run (the
    engine's delay matrix is static), whereas the simulator redraws it
    per message — expect distributional differences under jitter > 0."""
    rng = np.random.default_rng(seed)
    lat = base_latency + rng.uniform(0.0, max(jitter, 0.0), size=(n_workers, n_workers))
    dt = max(round_dt, 1e-12)
    return np.maximum(np.rint(lat / dt), 1).astype(np.int32)


def make_engine(worker: BatchedTMSNWorker, config: EngineConfig) -> TMSNEngine:
    """Build the right engine for ``config.mesh``.

    ``mesh=None`` or a 1-device mesh falls back to the single-device
    :class:`TMSNEngine` (the sharded path would only add collective
    overhead); a multi-device mesh with a ``workers`` axis builds the
    shard-mapped :class:`~repro.core.engine_sharded.ShardedTMSNEngine` —
    single-tier on a ``("workers",)`` mesh, hierarchical two-tier on a
    ``("pod", "workers")`` mesh.
    """
    mesh = config.mesh
    if mesh is None or mesh.size == 1:
        return TMSNEngine(worker, config)
    if "workers" not in mesh.axis_names:
        raise ValueError(f"engine mesh needs a 'workers' axis, got {mesh.axis_names}")
    from repro.core.engine_sharded import ShardedTMSNEngine

    return ShardedTMSNEngine(worker, config)
