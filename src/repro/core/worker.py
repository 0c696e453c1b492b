"""The worker API: the single public contract every TMSN substrate runs.

The paper's claim is that the protocol applies to *any* iterative
learner that can (a) improve a model locally and (b) put a number on
how good it is. This module is that claim as code: the two worker
protocols — one per fidelity level — plus the helpers the engines use
to keep the contract minimal for implementers.

Two fidelity levels, one vocabulary:

  * :class:`TMSNWorker` — the event-driven simulator's worker
    (fidelity 1, :mod:`repro.core.simulator`): scalar state objects,
    one worker instance per logical machine, Python floats for
    certificates.
  * :class:`BatchedTMSNWorker` — the round engines' worker
    (fidelity 2/3, :mod:`repro.core.engine` /
    :mod:`repro.core.engine_sharded`): all W workers stacked into one
    pytree with a leading ``(W,)`` axis, advanced one segment per round
    inside a single jitted computation.

Implementations: :class:`repro.boosting.batched_sparrow.BatchedSparrowWorker`
(the paper's boosting learner) and
:class:`repro.core.sgd_worker.BatchedSGDWorker` (transformer + AdamW —
TMSN as an async data-parallel training strategy).
``tests/test_worker_contract.py`` is the reusable conformance harness;
run it against any new worker before trusting a run.

Contract requirements (the engines silently assume all of them):

  * **Purity.** Every method must be pure and traceable — the engine
    jits whole round chunks with the worker computation inlined. No
    Python side effects, no data-dependent Python control flow.
  * **Leading worker axis.** Every per-worker quantity — including
    per-worker *constants* like feature-ownership masks and the PRNG
    streams — lives in the state pytree with a leading ``(W,)`` axis
    and shards with it. Inside the sharded engine's ``shard_map`` the
    methods see *local* shards (leading axis ``W_local``), so nothing
    per-worker may be closed over, and global worker identity must
    never be synthesized from a leaf's leading dimension.
  * **Masking.** ``scan_round`` / ``adopt_batch`` / ``resample_round``
    take per-worker masks; masked-out workers must come back bitwise
    unchanged with zero cost (the engines encode fail-stop and laggard
    credit as masks).
  * **Monotone certificates.** A scan may only keep or lower a
    worker's certificate, and adoption is accept-gated so it only
    lowers it. The gated-gossip and pod-mesh equivalence arguments
    lean on this (see :mod:`repro.core.engine_sharded`); a worker with
    a noisy estimate must carry the raw estimate separately and expose
    a monotone envelope (running minimum) as its certificate —
    :mod:`repro.core.sgd_worker` shows the pattern.
"""

from __future__ import annotations

import contextlib
from typing import Any, ContextManager, Protocol

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TMSNWorker",
    "BatchedTMSNWorker",
    "masked_rows",
    "has_resample_hooks",
    "export_payload_rows",
    "payload_bytes_from_export",
    "resolve_payload_bytes",
    "shared_data",
    "bind_shared_data",
]


class TMSNWorker(Protocol):
    """Duck-typed worker plugged into the event-driven simulator.

    State objects are opaque to the simulator; certificates are floats
    (lower = better).
    """

    def init_state(self, worker_id: int, seed: int) -> Any: ...

    def run_segment(self, state: Any) -> tuple[Any, float, bool]:
        """Run one scheduling quantum.

        Returns (new_state, cost_units, fired) where ``cost_units`` is
        the simulated compute cost of the segment (examples scanned,
        including any sampling pass) and ``fired`` is True if the worker
        found a better model during this segment.
        """
        ...

    def certificate(self, state: Any) -> float: ...

    def export_model(self, state: Any) -> Any: ...

    def adopt(self, state: Any, model: Any, certificate: float) -> Any:
        """Interrupt: replace (H, L) with the incoming pair."""
        ...

    def payload_bytes(self, model: Any) -> int: ...


class BatchedTMSNWorker(Protocol):
    """Duck-typed batched worker plugged into the round engines.

    All methods must be pure and traceable (the engine jits the whole
    round step, worker computation included). States are stacked
    pytrees with a leading worker axis; certificates are ``(W,)``
    float32 arrays (lower = better) and must be monotone non-increasing
    over rounds — see the module docstring for the full contract.

    Only the five required methods are mandatory. The optional members
    carry no-op / derived defaults: a worker may simply not define
    them (the engines probe with ``getattr`` via the module helpers
    below), or subclass this protocol to inherit the defaults
    explicitly.
    """

    # ----- required ----------------------------------------------------
    def init_batch(self, n_workers: int, seed: int) -> Any: ...

    def scan_round(self, state: Any, mask: jnp.ndarray) -> tuple[Any, jnp.ndarray, jnp.ndarray]:
        """Run one segment for every worker where ``mask`` is True.

        Returns (new_state, cost (W,), fired (W,)); masked-out workers
        must come back unchanged with zero cost.
        """
        ...

    def certificates(self, state: Any) -> jnp.ndarray: ...

    def export_models(self, state: Any) -> Any:
        """Stacked model pytree with leading worker axis (the broadcast
        payload; must be cheap — no recomputation). Leaves may be any
        shape/dtype: the engines' snapshot ring and payload accounting
        are derived from this pytree, never assumed."""
        ...

    def adopt_batch(
        self, state: Any, models: Any, certs: jnp.ndarray, take: jnp.ndarray
    ) -> tuple[Any, jnp.ndarray]:
        """Adopt ``models[i]``/``certs[i]`` wherever ``take[i]``;
        returns (new_state, cost (W,)). Must be the identity (zero
        cost) where ``take`` is False — the engines rely on this to
        skip or fuse the adopt step."""
        ...

    # ----- optional: sampling-phase hooks (no-op defaults) -------------
    def needs_resample(self, state: Any) -> jnp.ndarray:
        """(W,) bool — workers whose next segment is a resample.
        Workers without a sampling phase simply omit BOTH resample
        hooks; the engines then skip the resample plumbing entirely
        (:func:`has_resample_hooks`)."""
        return jnp.zeros_like(self.certificates(state), dtype=bool)

    def resample_round(self, state: Any, do: jnp.ndarray) -> tuple[Any, jnp.ndarray]:
        """Spend the segment of every worker where ``do`` on a resample;
        returns (new_state, cost (W,))."""
        return state, jnp.zeros_like(self.certificates(state), dtype=jnp.float32)

    # ----- optional: payload hooks (derived defaults) ------------------
    def export_payload_rows(self, state: Any, rows: jnp.ndarray) -> Any:
        """Gather just ``rows`` (a (k,) int array of worker-axis
        indices) of the broadcast payload. The sharded engine's
        candidate-selecting tiers use it — gated gossip ships only the
        top-k locally-improved candidate models instead of the full
        stack, and the pod-mesh cross-pod tier ships the top-k pending
        candidates per flush. Workers that omit it get the shared
        indexing fallback (:func:`export_payload_rows`, this default)."""
        return jax.tree_util.tree_map(lambda a: a[rows], self.export_models(state))

    def payload_bytes(self) -> int:
        """Per-worker broadcast payload size in bytes. Optional: when a
        worker omits it the engines derive the size from the exported
        model pytree itself (:func:`payload_bytes_from_export`), which
        cannot drift from reality; define it only when the logical wire
        format differs from the exported leaves."""
        raise NotImplementedError  # engines derive via resolve_payload_bytes

    # ----- optional: shared read-only inputs (no-op defaults) ----------
    def shared_data(self) -> Any:
        """Large read-only arrays the round methods read but no single
        worker owns (Sparrow's disk dataset), as a pytree. The engines
        pass them into each jitted chunk as arguments: closed over, jit
        would embed them in the compiled program as constants. ``None``
        (this default) = nothing shared."""
        return None

    def bind_shared_data(self, data: Any) -> ContextManager:
        """Context in which the round methods read ``data`` (traced
        stand-ins for :meth:`shared_data`) instead of the arrays the
        worker holds. The engines enter it while tracing a chunk."""
        return contextlib.nullcontext()


def masked_rows(cond: jnp.ndarray, new: Any, old: Any) -> Any:
    """Per-worker select over a stacked pytree: broadcast the ``(W,)``
    cond over each leaf's trailing dims. The canonical way to satisfy
    the contract's "masked-out workers come back bitwise unchanged"."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(cond.reshape(cond.shape + (1,) * (a.ndim - 1)), a, b),
        new,
        old,
    )


def has_resample_hooks(worker: BatchedTMSNWorker) -> bool:
    """True when the worker implements BOTH sampling-phase hooks. The
    engines check this once at build time and statically omit the
    resample branch from the round step for workers without a sampling
    phase — no per-round cond on an all-False vector."""
    return callable(getattr(worker, "needs_resample", None)) and callable(
        getattr(worker, "resample_round", None)
    )


def export_payload_rows(worker: BatchedTMSNWorker, state: Any, rows: jnp.ndarray) -> Any:
    """Candidate payloads for ``rows`` via the worker's optional
    ``export_payload_rows`` hook, falling back to indexing the full
    exported stack. The one shared fallback every engine tier uses."""
    hook = getattr(worker, "export_payload_rows", None)
    if hook is not None:
        return hook(state, rows)
    return jax.tree_util.tree_map(lambda a: a[rows], worker.export_models(state))


def shared_data(worker: BatchedTMSNWorker) -> Any:
    """The worker's optional :meth:`~BatchedTMSNWorker.shared_data`, or
    ``None`` for workers that omit it."""
    hook = getattr(worker, "shared_data", None)
    return hook() if callable(hook) else None


def bind_shared_data(worker: BatchedTMSNWorker, data: Any) -> ContextManager:
    """The worker's optional :meth:`~BatchedTMSNWorker.bind_shared_data`
    context, or a no-op one for workers that omit it."""
    hook = getattr(worker, "bind_shared_data", None)
    return hook(data) if callable(hook) else contextlib.nullcontext()


def payload_bytes_from_export(
    worker: BatchedTMSNWorker, n_workers: int, seed: int = 0
) -> int:
    """Per-worker payload bytes derived from the exported model pytree.

    ``jax.eval_shape`` traces ``export_models(init_batch(...))``
    abstractly — no arrays are materialized, so this is cheap even for
    transformer-sized workers — and the per-worker size is the summed
    leaf footprint divided by W. Because it measures the actual export,
    it cannot drift from the wire format the way a hand-maintained
    constant can (the Sparrow worker's hand value is pinned against
    this in tests)."""
    shapes = jax.eval_shape(lambda: worker.export_models(worker.init_batch(n_workers, seed)))
    total = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(shapes)
    )
    return total // max(n_workers, 1)


def resolve_payload_bytes(
    worker: BatchedTMSNWorker, n_workers: int, seed: int = 0
) -> int:
    """The payload size the engines account traffic with: the worker's
    own ``payload_bytes()`` when it defines one, else derived from the
    exported pytree."""
    hook = getattr(worker, "payload_bytes", None)
    # the Protocol default raises NotImplementedError; treat a worker
    # that inherited it (or omitted the method) identically
    if callable(hook) and getattr(hook, "__func__", hook) is not BatchedTMSNWorker.payload_bytes:
        return int(hook())
    return payload_bytes_from_export(worker, n_workers, seed)
