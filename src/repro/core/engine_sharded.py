"""Device-sharded TMSN engine (fidelity level 3).

:class:`~repro.core.engine.TMSNEngine` advances all W workers on one
device; faithful to the round semantics, but the paper's deployment is
*independent machines* that only exchange "something new" over
broadcast. This engine makes that physical: the stacked ``(W, ...)``
worker state is partitioned over a ``workers`` mesh axis with
``shard_map``, each device advances only its ``W_local = W / n_dev``
worker shard per round, and gossip is one explicit collective.

What changes relative to the single-device engine:

  * the ``(W, W, D)`` in-flight certificate buffer becomes a per-shard
    ``(W_local, W, D)`` slice — destination-sharded, source-global —
    so delivery (an argmin over sources) stays a local operation;
  * broadcast is an ``all_gather`` of the round's certificates, fired
    flags, and model payloads: O(W · payload) bytes per round on the
    interconnect (reported as ``SimResult.gossip_bytes_per_round``),
    instead of materializing every worker's full training state
    everywhere;
  * **gated gossip** (``EngineConfig.gossip_mode="gated"``) applies the
    paper's improvement gate to the interconnect itself: certificates
    and broadcast flags still all_gather densely (W·5 bytes — the
    cheap control plane), but model payloads move only for each
    device's top-``gossip_top_k`` locally-improved candidates, so the
    payload all_gather shrinks from O(W·payload) to O(n_dev·k·payload)
    and receivers resolve the global argmin among the gathered
    candidates through the existing in-flight/adopt machinery. Note
    eps still gates ACCEPTANCE only; the strict-improvement gate is
    what now also shapes traffic. Under uniform delay the adopted
    model is identical to dense mode — the per-round delivery argmin
    (lowest worker id on ties, both modes) is always its shard's
    minimum and therefore among the gathered candidates
    (``tests/test_sharded_engine.py`` pins this, including fail-stop,
    laggard credit, and the Pallas scan path). The argument leans on
    the worker-contract precondition that certificates are monotone
    non-increasing: the one receiver whose dense-mode best arrival is
    NOT the global minimum is the global-minimum worker itself
    (``push_mask`` excludes self), and monotonicity guarantees the
    same-shard runner-up that gating suppressed could never have been
    accepted by it anyway. Under heterogeneous
    delay matrices generations mix in the arrival slot and gated mode
    is an explicit, *measured* approximation (``bench_scaling.py``
    reports both modes);
  * **hierarchical pod mesh** (a 2-D ``("pod", "workers")`` mesh from
    ``launch/mesh.py::make_worker_mesh(pods=...)``): the interconnect
    itself becomes two-tier. Intra-pod gossip stays the per-round
    all_gather — but over the ``workers`` axis of ONE pod (ICI-class
    links). Cross-pod exchange is a SECOND in-flight tier: improvements
    accumulate in a per-worker pending mask (``EngineState.xpend``) and
    every ``EngineConfig.cross_pod_every_k`` rounds each device ships
    its top-``cross_pod_top_k`` pending candidates — freshest
    certificate, global worker id, model payload: the same top-k gated
    payload path — over the ``pod`` axis (DCN-class links). Receivers
    push the certificates into the in-flight buffer for cross-pod
    destinations only (same-pod destinations already heard tier 1) and
    scatter the payloads into their pod's ring replica. At
    ``cross_pod_every_k=1`` under uniform delay the pod engine is
    bit-identical to the flat all-device engine — the suppressed
    runner-up argument above applies per device, and a pending leftover
    that ships late is always dominated at every destination by a
    same-device candidate that shipped earlier (monotonicity), so it
    can neither be accepted nor displace an acceptable delivery
    (``tests/test_sharded_engine.py::TestPodMesh`` pins certs, history,
    and adoptions, dense and gated, incl. fail-stop and laggards). At
    k > 1 staleness is an explicit approximation — ``bench_scaling.py``
    reports the per-k certificate divergence and the ICI/DCN traffic
    split, never assumes them;
  * the ``(D, W)`` model-snapshot ring is *replicated* per shard but
    fed only by the gathered payloads (scattered by global worker id
    in gated mode), so any destination can look up any source's
    delayed snapshot without a second exchange. On a pod mesh the
    intra-pod gather differs between pods, so the ring is replicated
    only WITHIN a pod: the leading dim grows to ``n_pods * D`` and
    shards over the ``pod`` axis — one private ``(D, W)`` replica per
    pod, written by that pod's tier-1 gather plus the (globally
    identical) tier-2 flushes;
  * dispatch is chunked (``EngineConfig.rounds_per_dispatch``): the
    whole ``lax.scan`` over K rounds runs inside ONE ``shard_map``
    region, so per-chunk Python dispatch + host sync amortize over K
    rounds and the per-round collectives stay inside the compiled
    program. Target-crossing detection inside the scan uses a psum
    across shards;
  * **sparse in-flight state** (``EngineConfig.inflight_capacity > 0``)
    swaps the per-shard ``(W_local, W, D)`` buffer for bounded
    destination-sharded pending queues ``(W_local, C)`` fed by the same
    gathered tier-1 (and, on a pod mesh, tier-2 flush) candidates, with
    delivery + eps-gated accept + credit update fused into
    ``kernels/round_step.py`` — bit-identical to the dense buffer at
    sufficient capacity (``tests/test_sparse_inflight.py``), with every
    eviction counted in per-shard ``evicted`` / ``occ_peak`` partials;
  * **sparse control plane** (``EngineConfig.control_plane="sparse"``)
    removes the last dense-width exchange: instead of the per-round
    (W_tier,) certificate + flag all_gather (and its O(W_local·W)
    receiver-side scan/scatter), each device ships only its
    top-``gossip_top_k`` locally-improved candidates as (cert,
    global_id, round) triples — a fixed-size (n_dev, k) all_gather,
    OOB-padded — and receivers scatter them into the pending queues /
    in-flight buffer by global id: O(n_dev·k) per round, independent of
    W. Bit-identical to dense control under uniform delay — the
    suppressed-runner-up argument above applies unchanged, because the
    only receiver whose best arrival is not among the shipped top-k is
    a top-k sender itself, whose monotone local certificate already
    dominates anything suppressed (``tests/test_sparse_inflight.py``
    pins certificates, history, rounds and adoption counts across all
    substrates); a measured approximation under heterogeneous delay
    (``bench_scaling.py``, control-plane section). The
    ``kernels/round_step.py::queue_ingest`` kernel is the candidate-
    list counterpart of the fused delivery kernel;
  * traffic counters are per-shard partials of shape ``(n_dev,)``
    (summing inside the step would cost a ``psum`` per round);
    :meth:`~repro.core.result.TrafficCounters.from_shards` reduces
    them once at the end of the run — including the ICI/DCN split
    (``sent_dcn`` counts pushes that crossed a pod boundary).

Sharding contract (what lives per-shard vs replicated): per-shard —
the worker state pytree, certificates, alive/credit/clock vectors, the
destination-sharded in-flight buffer, the ``xpend`` pending mask, and
all traffic-counter partials (every ``EngineState`` field with leading
worker axis, partitioned over the whole mesh). Replicated — the round
counter, the target-crossing ``done`` flag (derived from a psum), and
on a 1-D mesh the snapshot ring; on a pod mesh the ring is replicated
per pod and sharded over the ``pod`` axis. Closed-over read-only data
(the disk dataset) is replicated to every device.

Equivalence contract: the per-worker math is elementwise over the
worker axis and delivery argmins run over the full source axis in both
engines, so on identical configs and seeds the sharded engine produces
final certificates *identical* to the single-device engine — including
fail-stop masks and laggard compute credit. ``tests/test_sharded_engine.py``
pins this on 8 forced host devices.

Serving edge: the train->serve publish hook
(:meth:`~repro.core.engine.TMSNEngine.attach_publisher` +
``EngineConfig.publish_every_k``/``publish_eps``) is inherited
unchanged — ``run()`` and :meth:`_maybe_publish` live on the base
class, publishing happens at host-side chunk boundaries, and the chunk
outputs (``state.certs``/``state.alive``/the worker pytree) are global
arrays under ``shard_map``, so exporting the best-certificate row
gathers exactly one worker's model regardless of sharding. The jitted
round step is untouched in both engines.

Worker contract addition: inside the shard-mapped step the
:class:`~repro.core.worker.BatchedTMSNWorker` methods see *local*
shards (leading axis ``W_local``, not ``W``). Workers must therefore
carry every per-worker constant (feature-ownership masks, worker ids
embedded in payloads, ...) in the state pytree — sharded along with it
— and never synthesize global worker identity from a leaf's leading
dimension. Shared read-only references (the disk dataset) are closed
over and replicated to every device, matching the paper's shared-disk
model.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import (
    EngineConfig,
    EngineState,
    RoundInfo,
    TMSNEngine,
    _dense_push_candidates,
    _inject_faults,
    _queue_push,
    _queue_push_candidates,
)
from repro.core.protocol import accepts, improves
from repro.core.worker import BatchedTMSNWorker, bind_shared_data, export_payload_rows


class _ShardConsts(NamedTuple):
    """Static per-worker vectors, passed as sharded step arguments (a
    closure capture would replicate them; these must arrive pre-sliced
    per shard)."""

    speed: jnp.ndarray  # (W,) -> (W_local,) per shard
    speed_norm: jnp.ndarray  # (W,) -> (W_local,)
    fail_round: jnp.ndarray  # (W,) -> (W_local,)
    delay_t: jnp.ndarray  # (W, W) [dst, src] -> (W_local, W)
    join_round: jnp.ndarray  # (W,) -> (W_local,) spare-activation round


class ShardedTMSNEngine(TMSNEngine):
    """Round-based TMSN run sharded over a ``workers`` mesh axis, or
    hierarchically over a two-tier ``(pod, workers)`` mesh."""

    def __init__(self, worker: BatchedTMSNWorker, config: EngineConfig) -> None:
        mesh = config.mesh
        if mesh is None:
            raise ValueError("ShardedTMSNEngine needs EngineConfig.mesh")
        names = tuple(mesh.axis_names)
        if names == ("workers",):
            self._n_pods = 1
            self._wpp = mesh.shape["workers"]  # devices on the workers axis
        elif names == ("pod", "workers"):
            self._n_pods = mesh.shape["pod"]
            self._wpp = mesh.shape["workers"]
        else:
            raise ValueError(
                "engine mesh must have axes ('workers',) or ('pod', 'workers'), "
                f"got {names}"
            )
        #: worker-axis partition spec: over both mesh axes on a pod mesh
        self._waxes = "workers" if self._n_pods == 1 else ("pod", "workers")
        self._n_dev = self._n_pods * self._wpp
        if config.n_workers % self._n_dev:
            raise ValueError(
                f"n_workers={config.n_workers} must divide over {self._n_dev} devices"
            )
        self._w_local = config.n_workers // self._n_dev
        super().__init__(worker, config)
        # place the shared data on every device once, not per dispatch
        self._shared = jax.device_put(self._shared, NamedSharding(mesh, P()))
        if self._n_pods > 1:
            # (W,) pod of each global worker id — closure-captured by
            # the shard-mapped step (replicated; a few hundred int32s),
            # used only to realize the FaultPlan partition window
            self._pod_of = jnp.arange(config.n_workers, dtype=jnp.int32) // (
                config.n_workers // self._n_pods
            )

    # ------------------------------------------------------------------
    def _build_chunk(self, length: int):
        """Chunk dispatcher: the whole K-round ``lax.scan`` runs inside
        one ``shard_map`` region (collectives and the cross-shard
        target-crossing psum stay inside the compiled program)."""
        mesh = self.config.mesh
        wx = self._waxes
        state_specs = EngineState(
            worker=P(wx),
            certs=P(wx),
            alive=P(wx),
            credit=P(wx),
            clock=P(wx),
            inflight=P(wx),
            # single-tier: replicated (fed by the all-device gather).
            # pod mesh: the intra-pod gather differs between pods, so
            # each pod keeps its OWN ring replica — leading (n_pods*D)
            # dim sharded over the pod axis, (D, W, ...) per pod.
            ring=P() if self._n_pods == 1 else P("pod"),
            round=P(),
            sent=P(wx),
            accepted=P(wx),
            discarded=P(wx),
            cost_total=P(wx),
            xpend=P(wx),
            sent_dcn=P(wx),
            evicted=P(wx),
            occ_peak=P(wx),
            dropped_inj=P(wx),
            corrupt_rej=P(wx),
        )
        # stacked over the chunk: leading scan axis, worker axis second
        infos_specs = RoundInfo(
            certs=P(None, wx),
            changed=P(None, wx),
            clock=P(None, wx),
            alive=P(None, wx),
        )
        consts_specs = _ShardConsts(
            speed=P(wx),
            speed_norm=P(wx),
            fail_round=P(wx),
            delay_t=P(wx),
            join_round=P(wx),
        )

        def _any_shard(x):
            # scalar "any worker on any shard" — replicated across shards
            axes = ("workers",) if self._n_pods == 1 else ("pod", "workers")
            return jax.lax.psum(jnp.any(x).astype(jnp.int32), axes) > 0

        def chunk_local(state: EngineState, consts: _ShardConsts, shared):
            body = self._chunk_body(
                lambda st: self._sharded_round_step(st, consts), _any_shard
            )
            with bind_shared_data(self.worker, shared):
                (state, _), infos = jax.lax.scan(
                    body, (state, jnp.zeros((), bool)), None, length=length
                )
            return state, infos

        # the worker's shared data is replicated: every device reads all of it
        shared_specs = jax.tree_util.tree_map(lambda _: P(), self._shared)
        step = jax.jit(
            jax.shard_map(
                chunk_local,
                mesh=mesh,
                in_specs=(state_specs, consts_specs, shared_specs),
                out_specs=(state_specs, infos_specs),
                check_vma=False,
            )
        )
        consts = _ShardConsts(
            speed=self._speed,
            speed_norm=self._speed_norm,
            fail_round=self._fail_round,
            # delay is stored [src, dst]; the step indexes [local dst, src]
            delay_t=jnp.transpose(self._delay),
            join_round=self._join_round,
        )
        return lambda state: step(state, consts, self._shared)

    def _init_state(self) -> EngineState:
        state = super()._init_state()
        zi = jnp.zeros((self._n_dev,), jnp.int32)
        state = state._replace(
            sent=zi,
            accepted=zi,
            discarded=zi,
            cost_total=jnp.zeros((self._n_dev,), jnp.float32),
            sent_dcn=zi,
            evicted=zi,
            occ_peak=zi,
            dropped_inj=zi,
            corrupt_rej=zi,
        )
        if self._n_pods > 1:
            # one private snapshot ring per pod (the intra-pod gather
            # feeds each pod differently): leading dim n_pods * D,
            # sharded over the pod axis to (D, W, ...) per pod. Initial
            # models are identical everywhere, so tiling is consistent.
            state = state._replace(
                ring=jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(
                        a[None], (self._n_pods,) + a.shape
                    ).reshape((-1,) + a.shape[1:]),
                    state.ring,
                )
            )
        return state

    def _gossip_split(self) -> tuple[int, int]:
        p = self._payload_bytes
        w = self.config.n_workers
        w_tier = w // self._n_pods  # workers gathered by the intra tier
        ici_ctrl, dcn_ctrl = self._control_split()
        if self.config.gossip_mode == "gated":
            # control plane (see _control_split) + k candidate payloads
            # per device; under dense control each payload also carries
            # its int32 global worker id (under sparse control the id
            # already rides in the control triple)
            k = min(int(self.config.gossip_top_k), self._w_local)
            ici = ici_ctrl + self._wpp * k * (p + (0 if self._control_sparse else 4))
        else:
            # dense payloads: every tier worker's model, every round;
            # the certificate/flag legs are the control plane
            ici = ici_ctrl + w_tier * p
        if self._n_pods == 1:
            return ici, 0
        # cross-pod tier: top-k pending candidates per device (control
        # triple or cert+id, plus payload), gathered over ALL devices
        # every cross_pod_every_k rounds — charged to the DCN class and
        # amortized per round (the control share is inside dcn_ctrl)
        kx = min(int(self.config.cross_pod_top_k), self._w_local)
        dcn = (self._n_dev * kx * p) // int(self.config.cross_pod_every_k)
        return ici, dcn + dcn_ctrl

    def _control_split(self) -> tuple[int, int]:
        """(ICI, DCN) control-plane bytes per round — the sub-share of
        :meth:`_gossip_split` that is certificates/flags/ids rather than
        model payloads.

        Dense control: the per-round (W_tier,) all_gather of f32 certs +
        bool broadcast flags — 5 bytes per tier worker, every round.
        Sparse control: (cert, global_id, round) triples for each
        device's top-k candidates — 12 bytes per candidate, n_dev·k of
        them, independent of W. The DCN tier ships cert+id per flush
        candidate under dense control (8 B) and the full triple under
        sparse (12 B), amortized over ``cross_pod_every_k``."""
        w_tier = self.config.n_workers // self._n_pods
        if self._control_sparse:
            k = min(int(self.config.gossip_top_k), self._w_local)
            ici = self._wpp * k * 12
        else:
            ici = w_tier * 5
        if self._n_pods == 1:
            return ici, 0
        kx = min(int(self.config.cross_pod_top_k), self._w_local)
        per = 12 if self._control_sparse else 8
        return ici, (self._n_dev * kx * per) // int(self.config.cross_pod_every_k)

    def _gossip_mode(self) -> str:
        return self.config.gossip_mode

    # ------------------------------------------------------------------
    def _dev_index(self):
        """Flat device index inside the shard-mapped step, matching the
        1-D device order (``pod`` is the slow axis of the 2-D mesh)."""
        if self._n_pods == 1:
            return jax.lax.axis_index("workers")
        return jax.lax.axis_index("pod") * self._wpp + jax.lax.axis_index("workers")

    def _export_rows(self, wstate, rows: jnp.ndarray):
        """Candidate payloads for ``rows`` — the shared optional-hook
        helper from :mod:`repro.core.worker` (the worker's
        ``export_payload_rows`` when defined, else the one indexing
        fallback both candidate-selecting tiers share)."""
        return export_payload_rows(self.worker, wstate, rows)

    def _sharded_round_step(
        self, state: EngineState, consts: _ShardConsts
    ) -> tuple[EngineState, RoundInfo]:
        cfg = self.config
        w, depth, wl = cfg.n_workers, self._depth, self._w_local
        r = state.round
        row_idx = jnp.arange(wl)
        local_ids = self._dev_index() * wl + row_idx  # global dst ids
        if self._has_joins:
            # sticky joins + fail-stop, with the joiner's laggard credit
            # reseeded on its activation round (see the single-device
            # engine for the full membership notes)
            alive = (state.alive | (r >= consts.join_round)) & (r < consts.fail_round)
            credit_in = jnp.where(r == consts.join_round, 0.0, state.credit)
        else:
            alive = state.alive & (r < consts.fail_round)
            credit_in = state.credit

        # last round's post-scan certificates, carried in the state (no
        # third certificates() call per round)
        certs0 = state.certs  # (wl,)

        # --- 1. deliver arrivals due this round (all-local: both
        # representations are destination-sharded with a global source
        # axis) --------------------------------------------------------------
        if self._capacity:
            # sparse: delivery argmin + accept gate + laggard credit are
            # one fused kernel call on the (wl, C) pending queue; the
            # queue stores the ring slot, so no delay lookup is needed
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
                state.inflight, certs0, alive, credit_in, consts.speed_norm, r
            )
        else:
            arr = state.inflight[:, :, 0]  # (wl dst, W src) certs
            arr_live = jnp.where(alive[:, None], arr, jnp.inf)
            best_src = jnp.argmin(arr_live, axis=1)  # (wl,) global src ids
            best_cert = arr_live[row_idx, best_src]
            take = accepts(certs0, best_cert, cfg.eps) & jnp.isfinite(best_cert)
            n_arrivals = jnp.sum(jnp.isfinite(arr), dtype=jnp.int32)
            sent_slot = (r - consts.delay_t[row_idx, best_src]) % depth
        n_taken = jnp.sum(take, dtype=jnp.int32)
        in_models = jax.tree_util.tree_map(
            lambda a: a[sent_slot, best_src], state.ring
        )

        def _adopt(operand):
            wstate, models, c, t = operand
            return self.worker.adopt_batch(wstate, models, c, t)

        # per-shard cond: a shard with no taker skips the adopt math
        wstate, adopt_cost = jax.lax.cond(
            jnp.any(take),
            _adopt,
            lambda operand: (operand[0], jnp.zeros((wl,), jnp.float32)),
            (state.worker, in_models, best_cert, take),
        )

        # --- 2.+3. shift the dense buffer, accrue compute credit (both
        # already folded into the fused kernel on the sparse path) ----------
        if not self._capacity:
            inflight = jnp.concatenate(
                [state.inflight[:, :, 1:], jnp.full((wl, w, 1), jnp.inf, jnp.float32)],
                axis=2,
            )
            credit = credit_in + consts.speed_norm
            active = alive & (credit >= 1.0 - 1e-6)
            credit = jnp.where(active, credit - 1.0, credit)

        # optional resample hooks: statically absent for workers
        # without a sampling phase (repro.core.worker.has_resample_hooks)
        if self._has_resample:
            need = self.worker.needs_resample(wstate) & active
            wstate, resample_cost = jax.lax.cond(
                jnp.any(need),
                lambda op: self.worker.resample_round(op[0], op[1]),
                lambda op: (op[0], jnp.zeros((wl,), jnp.float32)),
                (wstate, need),
            )
            scan_mask = active & ~need
        else:
            resample_cost = jnp.zeros((wl,), jnp.float32)
            scan_mask = active
        certs_pre = self.worker.certificates(wstate)
        wstate, scan_cost, fired = self.worker.scan_round(wstate, scan_mask)
        certs = self.worker.certificates(wstate)

        cost = adopt_cost + resample_cost + scan_cost
        clock = state.clock + cost / jnp.maximum(consts.speed, 1e-12)

        # --- 4+5. gossip, tier 1 (intra-pod / single-axis). Under the
        # DENSE control plane, certificates + broadcast flags gather
        # densely over the ``workers`` axis; model payloads gather for
        # every worker ("dense") or only for each device's top-k
        # locally-improved candidates ("gated"). Under the SPARSE
        # control plane (control_plane="sparse") there is NO (W_tier,)
        # leg at all: the exchange carries only each device's top-k
        # candidates as (cert, global_id) pairs — a fixed-size
        # (n_dev, k) gather, OOB-padded — and receivers scatter them
        # into the in-flight state by global id. On a 1-D mesh the
        # ``workers`` axis spans every device and this is the ONLY tier;
        # on a pod mesh it spans one pod, and (dense control only) the
        # gathered (W_pod,) control plane is scattered into the
        # (W,)-wide arrays at the pod's contiguous global-id block ----------
        improved = fired & improves(certs_pre, certs, 0.0) & scan_mask
        w_tier = w // self._n_pods  # workers visible to the intra tier
        pod_idx = jax.lax.axis_index("pod") if self._n_pods > 1 else None
        n_evicted = jnp.zeros((), jnp.int32)
        occ_pre_max = jnp.zeros((), jnp.int32)
        n_dropped = jnp.zeros((), jnp.int32)
        n_rejected = jnp.zeros((), jnp.int32)
        if self._control_sparse:
            kc = min(int(cfg.gossip_top_k), wl)
            cand_rows, cand_valid = self._top_k_candidates(improved, certs, kc)
            cand_ids = jnp.where(cand_valid, local_ids[cand_rows], w)
            cand_certs = jnp.where(cand_valid, certs[cand_rows], jnp.inf)
            if cfg.gossip_mode == "gated":
                # one collective: the (k,) control triples and the (k,)
                # candidate payloads ride together
                gathered = jax.lax.all_gather(
                    {
                        "certs": cand_certs,
                        "ids": cand_ids,
                        "models": self._export_rows(wstate, cand_rows),
                    },
                    "workers",
                    axis=0,
                    tiled=True,
                )  # every leg (wpp * kc, ...)
                ring = jax.tree_util.tree_map(
                    lambda buf, m: buf.at[r % depth, gathered["ids"]].set(
                        m, mode="drop"
                    ),
                    state.ring,
                    gathered["models"],
                )
            else:
                # dense payload plane, sparse control plane: every tier
                # worker's model still gathers, but only candidate rows
                # are ever referenced by the in-flight state, so only
                # those ring rows are written (scattered by global id;
                # invalid candidates point out of bounds and drop)
                gathered = jax.lax.all_gather(
                    {
                        "certs": cand_certs,
                        "ids": cand_ids,
                        "models": self.worker.export_models(wstate),
                    },
                    "workers",
                    axis=0,
                    tiled=True,
                )  # certs/ids: (wpp * kc,); models: (w_tier, ...)
                base = 0 if self._n_pods == 1 else pod_idx * w_tier
                rows_t = jnp.clip(gathered["ids"] - base, 0, w_tier - 1)
                ring = jax.tree_util.tree_map(
                    lambda buf, m: buf.at[r % depth, gathered["ids"]].set(
                        m[rows_t], mode="drop"
                    ),
                    state.ring,
                    gathered["models"],
                )
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
                    gathered["certs"],
                    gathered["ids"],
                    alive,
                    local_ids,
                    consts.delay_t,
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
                    gathered["certs"],
                    gathered["ids"],
                    alive,
                    local_ids,
                    consts.delay_t,
                    r=r,
                    dst_cert=certs,
                    fault=self._fault,
                    pod_of=self._pod_of,
                )
        elif cfg.gossip_mode == "gated":
            k = min(int(cfg.gossip_top_k), wl)
            cand_rows, cand_valid = self._top_k_candidates(improved, certs, k)
            bcast = jnp.zeros((wl,), bool).at[cand_rows].set(cand_valid)
            # ONE collective: tiled gathers are per-leaf, so the (wl,)
            # control plane and the (k,) payload leg ride together —
            # at gated payload sizes the per-collective launch latency
            # is the cost that matters
            gathered = jax.lax.all_gather(
                {
                    "certs": certs,
                    "bcast": bcast,
                    # un-improved candidate slots point out of bounds so
                    # the ring scatter drops them
                    "ids": jnp.where(cand_valid, local_ids[cand_rows], w),
                    "models": self._export_rows(wstate, cand_rows),
                },
                "workers",
                axis=0,
                tiled=True,
            )  # certs/bcast: (w_tier,); ids/models: (wpp * k, ...)
            tier_certs, tier_bcast = gathered["certs"], gathered["bcast"]
            ring = jax.tree_util.tree_map(
                lambda buf, m: buf.at[r % depth, gathered["ids"]].set(m, mode="drop"),
                state.ring,
                gathered["models"],
            )
        else:
            gathered = jax.lax.all_gather(
                {
                    "certs": certs,
                    "improved": improved,
                    "models": self.worker.export_models(wstate),
                },
                "workers",
                axis=0,
                tiled=True,
            )
            tier_certs, tier_bcast = gathered["certs"], gathered["improved"]
            # ring writes gated to broadcasters (only their entries are
            # ever read back), mirroring the single-device engine
            if self._n_pods == 1:
                ring = jax.tree_util.tree_map(
                    lambda buf, m: buf.at[r % depth].set(
                        jnp.where(
                            tier_bcast.reshape((-1,) + (1,) * (m.ndim - 1)),
                            m,
                            buf[r % depth],
                        )
                    ),
                    state.ring,
                    gathered["models"],
                )

        if not self._control_sparse:
            if self._n_pods == 1:
                certs_all, bcast_all = tier_certs, tier_bcast  # (W,)
            else:
                # scatter the pod-local control plane into global width;
                # pod p owns the contiguous global-id block
                # [p * W_pod, (p + 1) * W_pod)
                pod_gids = pod_idx * w_tier + jnp.arange(w_tier)
                certs_all = (
                    jnp.full((w,), jnp.inf, jnp.float32).at[pod_gids].set(tier_certs)
                )
                bcast_all = jnp.zeros((w,), bool).at[pod_gids].set(tier_bcast)
                if cfg.gossip_mode != "gated":
                    # dense intra-pod ring writes, scattered by global id
                    # into this pod's private ring replica (silent workers
                    # point out of bounds and drop)
                    ids = jnp.where(tier_bcast, pod_gids, w)
                    ring = jax.tree_util.tree_map(
                        lambda buf, m: buf.at[r % depth, ids].set(m, mode="drop"),
                        state.ring,
                        gathered["models"],
                    )

            if self._capacity:
                # tier-1 push into the (wl, C) pending queues: the
                # gathered control plane is dense-width in both gossip
                # modes, so one (W,) candidate score serves dense and
                # gated alike; on a pod mesh bcast_all is zero outside
                # this pod
                (
                    inflight,
                    n_pushed,
                    n_evicted,
                    occ_pre_max,
                    n_dropped,
                    n_rejected,
                ) = _queue_push(
                    inflight,
                    jnp.where(bcast_all, certs_all, jnp.inf),
                    alive,
                    local_ids,
                    consts.delay_t,
                    r,
                    depth,
                    dst_cert=certs,
                    fault=self._fault,
                    pod_of=self._pod_of,
                )
            elif self._fault is None:
                d_idx = jnp.arange(depth)[None, None, :]
                # push_mask[local dst, global src, d]; on a pod mesh
                # bcast_all is zero outside this pod, so tier-1 pushes
                # stay intra-pod
                push_mask = (
                    bcast_all[None, :, None]
                    & alive[:, None, None]
                    & (local_ids[:, None] != jnp.arange(w)[None, :])[:, :, None]
                    & (d_idx == (consts.delay_t[:, :, None] - 1))
                )
                inflight = jnp.where(push_mask, certs_all[None, :, None], inflight)
                n_pushed = jnp.sum(push_mask, dtype=jnp.int32)
            else:
                # faulted dense push: per-edge (wl, W) certificate matrix
                # so _inject_faults can drop/corrupt/reject single edges
                # (mirrors the single-device engine's faulted branch)
                push2 = (
                    bcast_all[None, :]
                    & alive[:, None]
                    & (local_ids[:, None] != jnp.arange(w)[None, :])
                )
                cert_mat = jnp.where(push2, certs_all[None, :], jnp.inf)
                src_mat = jnp.broadcast_to(
                    jnp.arange(w, dtype=jnp.int32)[None, :], (wl, w)
                )
                cert_mat, _, _, n_dropped, n_rejected = _inject_faults(
                    self._fault,
                    self._pod_of,
                    r,
                    local_ids.astype(jnp.int32),
                    src_mat,
                    cert_mat,
                    None,
                    certs,
                    depth,
                )
                d_idx = jnp.arange(depth)[None, None, :]
                push_mask = jnp.isfinite(cert_mat)[:, :, None] & (
                    d_idx == (consts.delay_t[:, :, None] - 1)
                )
                inflight = jnp.where(push_mask, cert_mat[:, :, None], inflight)
                n_pushed = jnp.sum(push2, dtype=jnp.int32)  # logical sends

        # --- gossip, tier 2 (cross-pod, DCN): improvements accumulate
        # in the pending mask and the freshest certificates flush over
        # the ``pod`` axis every cross_pod_every_k rounds — the paper's
        # "tell me something new" applied to the interconnect hierarchy.
        # Each device ships its top cross_pod_top_k pending candidates
        # (the PR 3 gated payload path); receivers scatter the payloads
        # into their pod's ring replica and push the certificates into
        # the in-flight buffer for cross-pod destinations only (same-pod
        # destinations already got them from tier 1) ------------------------
        xpend = state.xpend
        n_pushed_x = jnp.zeros((), jnp.int32)
        if self._n_pods > 1:
            xpend = xpend | improved
            kx = min(int(cfg.cross_pod_top_k), wl)
            src_pod = jnp.arange(w) // w_tier  # (W,) pod of each global id

            def _flush(args):
                xpend, inflight, ring = args
                rows, valid = self._top_k_candidates(xpend, certs, kx)
                gx = jax.lax.all_gather(
                    {
                        "certs": certs[rows],
                        "ids": jnp.where(valid, local_ids[rows], w),
                        "models": self._export_rows(wstate, rows),
                    },
                    ("pod", "workers"),
                    axis=0,
                    tiled=True,
                )  # (n_dev * kx, ...), flat-device order (pod-major)
                ring = jax.tree_util.tree_map(
                    lambda buf, m: buf.at[r % depth, gx["ids"]].set(m, mode="drop"),
                    ring,
                    gx["models"],
                )
                flushed = jnp.zeros((wl,), bool).at[rows].set(valid)
                if self._control_sparse:
                    # sparse control: push the gathered flush candidates
                    # directly by global id — no (W,)-wide scatter. The
                    # cross-pod mask (same-pod destinations already
                    # heard tier 1) folds into candidate validity.
                    pod_of = jnp.clip(gx["ids"], 0, w - 1) // w_tier
                    valid_x = (gx["ids"] < w) & (pod_of != pod_idx)
                    ids_x = jnp.where(valid_x, gx["ids"], w)
                    certs_x = jnp.where(valid_x, gx["certs"], jnp.inf)
                    if self._capacity:
                        inflight, nx, ne, occ, nd, nr = _queue_push_candidates(
                            inflight,
                            certs_x,
                            ids_x,
                            alive,
                            local_ids,
                            consts.delay_t,
                            r,
                            depth,
                            cfg.round_step_impl,
                            dst_cert=certs,
                            fault=self._fault,
                            pod_of=self._pod_of,
                        )
                        return (xpend & ~flushed, inflight, ring, nx, ne, occ, nd, nr)
                    inflight, nx, nd, nr = _dense_push_candidates(
                        inflight,
                        certs_x,
                        ids_x,
                        alive,
                        local_ids,
                        consts.delay_t,
                        r=r,
                        dst_cert=certs,
                        fault=self._fault,
                        pod_of=self._pod_of,
                    )
                    z = jnp.zeros((), jnp.int32)
                    return (xpend & ~flushed, inflight, ring, nx, z, z, nd, nr)
                xcerts = (
                    jnp.full((w,), jnp.inf, jnp.float32)
                    .at[gx["ids"]]
                    .set(gx["certs"], mode="drop")
                )
                xbcast = (
                    jnp.zeros((w,), bool)
                    .at[gx["ids"]]
                    .set(jnp.ones_like(gx["ids"], bool), mode="drop")
                )
                if self._capacity:
                    # same queue push as tier 1, with the candidate score
                    # masked to cross-pod sources (same-pod destinations
                    # already heard these via tier 1)
                    inflight, nx, ne, occ, nd, nr = _queue_push(
                        inflight,
                        jnp.where(xbcast & (src_pod != pod_idx), xcerts, jnp.inf),
                        alive,
                        local_ids,
                        consts.delay_t,
                        r,
                        depth,
                        dst_cert=certs,
                        fault=self._fault,
                        pod_of=self._pod_of,
                    )
                    return (xpend & ~flushed, inflight, ring, nx, ne, occ, nd, nr)
                z = jnp.zeros((), jnp.int32)
                nd = nr = z
                xpush2 = (
                    xbcast[None, :]
                    & alive[:, None]
                    # only cross-pod destinations (self-exclusion implied)
                    & (src_pod != pod_idx)[None, :]
                )
                xcert_mat = jnp.where(xpush2, xcerts[None, :], jnp.inf)
                if self._fault is not None:
                    src_mat = jnp.broadcast_to(
                        jnp.arange(w, dtype=jnp.int32)[None, :], (wl, w)
                    )
                    xcert_mat, _, _, nd, nr = _inject_faults(
                        self._fault,
                        self._pod_of,
                        r,
                        local_ids.astype(jnp.int32),
                        src_mat,
                        xcert_mat,
                        None,
                        certs,
                        depth,
                    )
                d_idx = jnp.arange(depth)[None, None, :]
                xpush = jnp.isfinite(xcert_mat)[:, :, None] & (
                    d_idx == (consts.delay_t[:, :, None] - 1)
                )
                inflight = jnp.where(xpush, xcert_mat[:, :, None], inflight)
                return (
                    xpend & ~flushed,
                    inflight,
                    ring,
                    jnp.sum(xpush2, dtype=jnp.int32),
                    z,
                    z,
                    nd,
                    nr,
                )

            if int(cfg.cross_pod_every_k) == 1:
                xpend, inflight, ring, n_pushed_x, ne_x, occ_x, nd_x, nr_x = _flush(
                    (xpend, inflight, ring)
                )
            else:
                # `r` is replicated, so every device takes the same
                # branch and the pod-axis collective stays uniform
                (
                    xpend,
                    inflight,
                    ring,
                    n_pushed_x,
                    ne_x,
                    occ_x,
                    nd_x,
                    nr_x,
                ) = jax.lax.cond(
                    (r % int(cfg.cross_pod_every_k)) == 0,
                    _flush,
                    lambda args: (
                        args[0],
                        args[1],
                        args[2],
                        jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.int32),
                    ),
                    (xpend, inflight, ring),
                )
            n_evicted = n_evicted + ne_x
            occ_pre_max = jnp.maximum(occ_pre_max, occ_x)
            n_dropped = n_dropped + nd_x
            n_rejected = n_rejected + nr_x

        new_state = EngineState(
            worker=wstate,
            certs=certs,
            alive=alive,
            credit=credit,
            clock=clock,
            inflight=inflight,
            ring=ring,
            round=r + 1,
            # (1,)-shaped per-shard partials; (n_dev,) globally
            sent=state.sent + n_pushed + n_pushed_x,
            accepted=state.accepted + n_taken,
            discarded=state.discarded + (n_arrivals - n_taken),
            cost_total=state.cost_total + jnp.sum(cost),
            xpend=xpend,
            sent_dcn=state.sent_dcn + n_pushed_x,
            evicted=state.evicted + n_evicted,
            occ_peak=jnp.maximum(state.occ_peak, occ_pre_max),
            dropped_inj=state.dropped_inj + n_dropped,
            corrupt_rej=state.corrupt_rej + n_rejected,
        )
        info = RoundInfo(
            certs=certs, changed=take | improved, clock=clock, alive=alive
        )
        return new_state, info


def sharded_engine_available(min_devices: int = 2) -> bool:
    """True when the current backend exposes enough devices to shard
    over (CI forces 8 host devices via ``XLA_FLAGS``); the sharded test
    modules key their skip conditions on this."""
    return len(jax.devices()) >= min_devices


__all__ = ["ShardedTMSNEngine", "sharded_engine_available"]
