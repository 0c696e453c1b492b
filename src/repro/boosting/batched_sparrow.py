"""Batched Sparrow: W workers as stacked ``(W, ...)`` pytrees.

The per-worker computation is exactly :mod:`repro.boosting.sparrow`'s
scan/fire/resample/adopt logic, re-expressed so every branch is an
elementwise select and the chunk scan is ``vmap(scan_chunk)`` over the
worker axis — including the Pallas ``kernels/edge_scan`` path when
``ScannerConfig.use_kernel`` is set (``vmap`` of a ``pallas_call``
prepends a batch grid dimension, so all W histogram accumulations run
in one kernel launch).

Plugged into :class:`repro.core.engine.TMSNEngine` this advances all W
workers one segment per round in a single jitted computation; the
event-driven simulator with the unbatched :class:`SparrowWorker`
remains the fidelity-1 oracle (``tests/test_engine.py`` pins the
per-segment equivalence of the two).

The same methods trace inside the sharded engine's shard-mapped round
step, where the leading axis is the *local* worker count: everything
per-worker (including the feature-ownership masks) lives in the state
pytree and shards with it, while the disk dataset (``xb``/``y``) is a
closed-over shared read-only reference, replicated per device exactly
as the paper's shared-disk model prescribes.

Deviations from the unbatched worker, both bounded and test-pinned:

  * adoption cost is charged on the round it happens instead of via
    ``pending_cost`` on the next segment (same totals, simpler state);
  * Python-float certificate accumulation becomes float32 array math
    (differences are at the 1e-6 level).
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.boosting.scanner import (
    SampleState,
    ScannerState,
    init_scanner,
    reset_after_fire,
    reset_after_fruitless_pass,
    scan_chunk,
)
from repro.boosting.sparrow import (
    STUMP_EVAL_COST,
    SparrowWorkerBase,
    draw_sample,
)
from repro.core.ess import effective_sample_size
from repro.core.worker import masked_rows
from repro.boosting.stumps import (
    StumpModel,
    alpha_from_gamma,
    append_stump,
    empty_model,
    model_payload_bytes,
    predict_margin_delta,
)


class BatchedSparrowState(NamedTuple):
    """Stacked per-worker state; every leaf has a leading (W,) axis.

    Per-worker *constants* (the feature-ownership masks) live here too,
    not on the worker object: inside the sharded engine's shard-mapped
    round step each device sees only its local slice of the state, so
    anything indexed by worker identity must shard along with it — a
    closed-over ``(W, d)`` array would arrive fully replicated and
    misaligned with the ``(W_local, ...)`` leaves.
    """

    model: StumpModel  # fields (W, T), count (W,)
    cert: jnp.ndarray  # (W,) f32
    scanner: ScannerState  # leaves (W, ...)
    sample: SampleState  # leaves (W, m, ...)
    disk_margin: jnp.ndarray  # (W, n) H_{disk_t}(x) over the disk set
    #: (W,) i32 stump count the disk margins are current to (the same for
    #: every example: a resample refreshes the whole set); -1 = margins
    #: discarded by an adoption, read as H_0 = 0 at the next resample
    disk_t: jnp.ndarray
    key: jax.Array  # (W, 2) PRNG keys
    needs_resample: jnp.ndarray  # (W,) bool
    fires: jnp.ndarray  # (W,) i32
    resamples: jnp.ndarray  # (W,) i32
    sample_model_count: jnp.ndarray  # (W,) i32
    scan_since_resample: jnp.ndarray  # (W,) f32
    feat_mask: jnp.ndarray  # (W, d) bool — feature ownership (constant)


# per-worker select over a stacked pytree — the contract-level helper
# from repro.core.worker, kept under its historical local name
_bwhere = masked_rows


def common_prefix_len(a: StumpModel, b: StumpModel) -> jnp.ndarray:
    """Jit-safe length of the shared stump prefix of two (unbatched)
    models (the traced counterpart of ``SparrowWorker._common_prefix``)."""
    same = (
        (a.feat == b.feat)
        & (a.thr == b.thr)
        & (a.sign == b.sign)
        & (a.alpha == b.alpha)
    )
    slots = jnp.arange(a.capacity)
    same = same & (slots < jnp.minimum(a.count, b.count))
    return jnp.sum(jnp.cumprod(same.astype(jnp.int32))).astype(jnp.int32)


class BatchedSparrowWorker(SparrowWorkerBase):
    """Implements :class:`repro.core.worker.BatchedTMSNWorker` for
    Sparrow — the boosting instantiation of the worker contract."""

    # ----- engine protocol hooks --------------------------------------
    def init_batch(self, n_workers: int, seed: int) -> BatchedSparrowState:
        cfg = self.config
        if n_workers != cfg.n_workers:
            raise ValueError(f"engine W={n_workers} != SparrowConfig.n_workers={cfg.n_workers}")
        # same per-worker streams as TMSNSimulator: PRNGKey(seed + 1000*i)
        keys = jnp.stack([jax.random.PRNGKey(seed + 1000 * i) for i in range(n_workers)])

        def _init_one(key: jax.Array):
            model = empty_model(cfg.capacity)
            disk_margin = jnp.zeros((self.n,), jnp.float32)
            key, sub = jax.random.split(key)
            sample = draw_sample(sub, self.xb, self.y, model, disk_margin, cfg.sample_size)
            return model, sample, key

        model, sample, keys = jax.vmap(_init_one)(keys)
        scanner = jax.vmap(lambda _: init_scanner(self.d, cfg.scanner))(
            jnp.arange(n_workers)
        )
        zeros_i = jnp.zeros((n_workers,), jnp.int32)
        return BatchedSparrowState(
            model=model,
            cert=jnp.zeros((n_workers,), jnp.float32),
            scanner=scanner,
            sample=sample,
            disk_margin=jnp.zeros((n_workers, self.n), jnp.float32),
            disk_t=jnp.zeros((n_workers,), jnp.int32),
            key=keys,
            needs_resample=jnp.zeros((n_workers,), bool),
            fires=zeros_i,
            resamples=zeros_i,
            sample_model_count=zeros_i,
            scan_since_resample=jnp.zeros((n_workers,), jnp.float32),
            feat_mask=self._feat_masks,
        )

    def certificates(self, state: BatchedSparrowState) -> jnp.ndarray:
        return state.cert

    def export_models(self, state: BatchedSparrowState) -> StumpModel:
        return state.model

    def export_payload_rows(
        self, state: BatchedSparrowState, rows: jnp.ndarray
    ) -> StumpModel:
        """Gather just ``rows`` of the broadcast payload — the sharded
        engine's candidate-selecting tiers both use this hook: gated
        intra-pod gossip ships each device's top-k improved candidate
        models instead of the full (W_local, ...) stack, and the
        pod-mesh engine's cross-pod (DCN) tier ships each device's
        top-k *pending* candidates every ``cross_pod_every_k`` rounds.
        The rows carry whatever the worker currently holds, so a
        cross-pod flush always exports the FRESHEST model for a worker
        whose certificate kept improving between flushes."""
        return jax.tree_util.tree_map(lambda a: a[rows], state.model)

    def needs_resample(self, state: BatchedSparrowState) -> jnp.ndarray:
        return state.needs_resample

    def payload_bytes(self) -> int:
        return model_payload_bytes(empty_model(self.config.capacity))

    def shared_data(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """The disk dataset, read by the resample segment."""
        return self.xb, self.y

    @contextlib.contextmanager
    def bind_shared_data(self, data: tuple[jnp.ndarray, jnp.ndarray]):
        held = self.xb, self.y
        self.xb, self.y = data
        try:
            yield
        finally:
            self.xb, self.y = held

    # ----- one scan segment for every masked worker -------------------
    def scan_round(
        self, state: BatchedSparrowState, mask: jnp.ndarray
    ) -> tuple[BatchedSparrowState, jnp.ndarray, jnp.ndarray]:
        cfg = self.config
        m = cfg.sample_size
        scan = functools.partial(scan_chunk, config=cfg.scanner)
        scanner_s, sample_s, info = jax.vmap(scan)(
            state.scanner, state.sample, state.model, state.feat_mask
        )
        chunk = min(cfg.scanner.chunk_size, m)
        maskf = mask.astype(jnp.float32)
        cost = (chunk * cfg.mem_read_cost + STUMP_EVAL_COST * info.stump_evals) * maskf

        # --- fire: append the certified stump, advance the certificate ---
        gamma = info.cert_gamma
        alpha = alpha_from_gamma(gamma)
        model2 = jax.vmap(append_stump)(state.model, info.feat, info.thr, info.sign, alpha)
        grew = model2.count > state.model.count
        fired = info.fired & mask & grew  # at capacity: no growth, no certificate claim
        cert = jnp.where(
            fired, state.cert + 0.5 * jnp.log1p(-4.0 * jnp.square(gamma)), state.cert
        )
        model = _bwhere(fired, model2, state.model)

        fire_scanner = jax.vmap(
            lambda s, g: reset_after_fire(s, cfg.keep_gamma_on_fire, cfg.scanner, g)
        )(scanner_s, info.emp_gamma)
        fruitless = (~info.fired) & info.full_pass & mask
        fruitless_scanner = jax.vmap(reset_after_fruitless_pass)(scanner_s)
        scanner = _bwhere(
            fired, fire_scanner, _bwhere(fruitless, fruitless_scanner, scanner_s)
        )

        # --- ESS staleness / gamma-exhaustion -> schedule resample ---
        wts = jnp.exp(
            jnp.clip(-sample_s.y * (sample_s.margin_l - sample_s.margin_s), -30.0, 30.0)
        )
        ess = jax.vmap(effective_sample_size)(wts)
        stale = ess / m < cfg.ess_threshold
        advanced = state.model.count > state.sample_model_count
        exhausted = (scanner_s.gamma <= 2e-4) & advanced
        needs = jnp.where(
            fired, stale, jnp.where(fruitless, stale | exhausted, state.needs_resample)
        )

        new_state = state._replace(
            model=model,
            cert=cert,
            scanner=scanner,
            sample=sample_s,
            needs_resample=needs,
            fires=state.fires + fired.astype(jnp.int32),
            scan_since_resample=state.scan_since_resample + cost,
        )
        # masked-out workers come back untouched
        new_state = _bwhere(mask, new_state, state)
        return new_state, cost, fired

    # ----- resample segment (rare; sequential over workers so the full
    # disk pass never materializes a (W, n, T) intermediate, and in
    # place, so the (W, ...) state is never copied) ---------------------
    def resample_round(
        self, state: BatchedSparrowState, do: jnp.ndarray
    ) -> tuple[BatchedSparrowState, jnp.ndarray]:
        cfg = self.config

        def _resample_one(st: BatchedSparrowState):
            stale = st.disk_t < 0
            t_from = jnp.maximum(st.disk_t, 0)
            delta = predict_margin_delta(
                st.model, self.xb, jnp.full((self.n,), t_from, jnp.int32)
            )
            evals = (
                self.n * jnp.minimum(st.model.count - t_from, st.model.capacity)
            ).astype(jnp.float32)
            disk_margin = jnp.where(stale, 0.0, st.disk_margin) + delta
            disk_t = st.model.count
            key, sub = jax.random.split(st.key)
            sample = draw_sample(sub, self.xb, self.y, st.model, disk_margin, cfg.sample_size)
            cost = self.n * cfg.disk_read_cost + STUMP_EVAL_COST * evals
            if cfg.parallel_sampler:
                cost = jnp.maximum(cost - st.scan_since_resample, 0.0)
            scanner = reset_after_fire(st.scanner, True, cfg.scanner)._replace(
                pos=jnp.zeros((), jnp.int32)
            )
            new = st._replace(
                sample=sample,
                disk_margin=disk_margin,
                disk_t=disk_t,
                key=key,
                needs_resample=jnp.zeros((), bool),
                scanner=scanner,
                resamples=st.resamples + 1,
                sample_model_count=st.model.count,
                scan_since_resample=jnp.zeros((), jnp.float32),
            )
            return new, jnp.asarray(cost, jnp.float32)

        def _one(i, carry):
            states, costs = carry
            st = jax.tree_util.tree_map(lambda a: a[i], states)
            new, cost = jax.lax.cond(
                do[i], _resample_one, lambda s: (s, jnp.zeros((), jnp.float32)), st
            )
            states = jax.tree_util.tree_map(lambda a, v: a.at[i].set(v), states, new)
            return states, costs.at[i].set(cost)

        costs = jnp.zeros(do.shape, jnp.float32)
        return jax.lax.fori_loop(0, do.shape[0], _one, (state, costs))

    # ----- adoption (interrupt + replace (H, L)) -----------------------
    def adopt_batch(
        self,
        state: BatchedSparrowState,
        models: StumpModel,
        certs: jnp.ndarray,
        take: jnp.ndarray,
    ) -> tuple[BatchedSparrowState, jnp.ndarray]:
        """Vectorized counterpart of ``SparrowWorker.adopt``: incremental
        margin transfer across the shared stump prefix, elementwise."""
        cfg = self.config
        m = cfg.sample_size

        def _adopt_one(st: BatchedSparrowState, new_model: StumpModel, new_cert):
            p = common_prefix_len(st.model, new_model)
            xb = st.sample.xb
            catchup = predict_margin_delta(st.model, xb, st.sample.t_l)
            evals = jnp.sum(
                jnp.clip(st.model.count - st.sample.t_l, 0, None)
            ).astype(jnp.float32)
            full_old = st.sample.margin_l + catchup
            pfx = jnp.full((m,), p, jnp.int32)
            old_sfx = predict_margin_delta(st.model, xb, pfx)
            new_sfx = predict_margin_delta(new_model, xb, pfx)
            m_new = full_old - old_sfx + new_sfx
            evals += (m * ((st.model.count - p) + (new_model.count - p))).astype(jnp.float32)
            sample = st.sample._replace(
                margin_l=m_new,
                t_l=jnp.full_like(st.sample.t_l, new_model.count),
            )
            # the disk margins survive when the shared prefix covers
            # them; otherwise they are marked stale, not rewritten
            disk_t = jnp.where(p >= st.disk_t, st.disk_t, -1)
            cost = STUMP_EVAL_COST * evals * cfg.mem_read_cost
            new = st._replace(
                model=new_model,
                cert=jnp.asarray(new_cert, jnp.float32),
                sample=sample,
                disk_t=disk_t,
                scanner=reset_after_fire(st.scanner, True, cfg.scanner),
            )
            return new, cost

        adopted, cost = jax.vmap(_adopt_one)(state, models, certs)
        new_state = _bwhere(take, adopted, state)
        return new_state, cost * take.astype(jnp.float32)
