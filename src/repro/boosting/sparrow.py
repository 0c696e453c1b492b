"""A Sparrow worker (paper §4) pluggable into the TMSN simulator.

Each worker owns a subset of the features (feature-based
parallelization), keeps the full "disk" dataset as a shared read-only
reference, maintains an in-memory weighted sample of size ``m``, and
alternates between Scanning and Sampling (as in the paper's current
implementation — footnote 3).

Certificates: the log-potential bound ``L_t = sum_k 1/2 log(1 - 4 g_k^2)``
over the certified edges ``g_k`` of the stumps in the strong rule. The
stopping rule guarantees each certified edge holds w.h.p., which makes
``exp(L_t)`` a sound high-probability upper bound on the true potential
``Z(H_t)`` — exactly the "certificate of quality" of §4.2.

Cost model (simulated seconds = cost units / worker speed):
    cost = examples_touched + STUMP_EVAL_COST * incremental_stump_evals
A fresh sampling pass touches all n disk examples and pays incremental
weight refresh on them too (the paper: "run time is now dominated by the
time it takes to create new samples").
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.boosting.sampler import minimal_variance_sample
from repro.boosting.scanner import (
    SampleState,
    ScannerConfig,
    ScannerState,
    init_scanner,
    reset_after_fire,
    reset_after_fruitless_pass,
    scan_chunk,
)
from repro.boosting.stumps import (
    StumpModel,
    alpha_from_gamma,
    append_stump,
    empty_model,
    model_payload_bytes,
    predict_margin_delta,
)
from repro.core.ess import effective_sample_size

STUMP_EVAL_COST = 0.1  # relative cost of one incremental stump eval vs one example read


def feature_ownership_masks(d: int, n_workers: int, redundancy: int = 1) -> np.ndarray:
    """(n_workers, d) bool ownership masks (feature-based parallelization,
    §4): feature j belongs to workers {j mod k, ..., j mod k + r - 1}."""
    k = n_workers
    r = max(1, min(redundancy, k))
    fmod = np.arange(d) % k
    masks = np.zeros((k, d), bool)
    for wid in range(k):
        for j in range(r):
            masks[wid] |= fmod == ((wid + j) % k)
    return masks


def draw_sample(
    key: jax.Array,
    disk_xb: jnp.ndarray,
    disk_y: jnp.ndarray,
    model: StumpModel,
    disk_margin: jnp.ndarray,
    sample_size: int,
) -> SampleState:
    """Draw a fresh in-memory sample from the disk set (pure jnp, so the
    batched worker can ``vmap`` it over stacked per-worker states)."""
    w = jnp.exp(jnp.clip(-disk_y * disk_margin, -30.0, 30.0))
    idx = minimal_variance_sample(key, w, sample_size)
    margin = disk_margin[idx]
    return SampleState(
        xb=disk_xb[idx],
        y=disk_y[idx],
        margin_s=margin,
        margin_l=margin,
        t_l=jnp.full((sample_size,), model.count, jnp.int32),
    )


@dataclasses.dataclass(frozen=True)
class SparrowConfig:
    sample_size: int = 8192  # m — in-memory sample size
    capacity: int = 256  # strong-rule capacity T_max
    scanner: ScannerConfig = ScannerConfig()
    ess_threshold: float = 0.1  # resample when n_eff/m drops below this
    keep_gamma_on_fire: bool = True  # False = paper pseudocode (reset to gamma0)
    n_workers: int = 1  # for feature ownership
    #: beyond-paper: each feature owned by r workers (r>1 keeps the full
    #: hypothesis space reachable when workers fail-stop; EXPERIMENTS.md
    #: §Repro shows r=1 loses certificate progress under failures)
    ownership_redundancy: int = 1
    #: memory-hierarchy pricing (paper §1/§5: scanning the in-memory
    #: sample is much cheaper than streaming the full set from disk).
    #: Scan-chunk cost is scaled by mem_read_cost; the Sampler's full
    #: pass is charged disk_read_cost per example.
    mem_read_cost: float = 1.0
    disk_read_cost: float = 1.0
    #: beyond-paper (the paper's own footnote 3 + conclusion "run time is
    #: now dominated by ... creating new samples"): run the Sampler on a
    #: second core, overlapped with scanning. The scanner only blocks for
    #: the part of the disk pass not already covered by scan time since
    #: the previous resample.
    parallel_sampler: bool = False


class SparrowState(NamedTuple):
    worker_id: int
    model: StumpModel
    cert: float  # log-potential bound (lower = better)
    scanner: ScannerState
    sample: SampleState
    # disk-side lazy weight bookkeeping
    disk_margin: jnp.ndarray  # (n,)
    disk_t: jnp.ndarray  # (n,) i32
    key: jax.Array
    needs_resample: bool
    pending_cost: float  # cost incurred by adopt (charged on next segment)
    fires: int
    resamples: int
    sample_model_count: int  # stump count when the current sample was drawn
    scan_since_resample: float = 0.0  # for the parallel-sampler overlap model


class SparrowWorkerBase:
    """Shared disk-set/config initialization for the unbatched and
    batched Sparrow workers: dtype coercion, sample-size validation,
    and the feature-ownership table live in ONE place so the two
    workers (whose equivalence tests pin segment-for-segment) cannot
    silently diverge on setup."""

    def __init__(
        self,
        disk_xb: jnp.ndarray,
        disk_y: jnp.ndarray,
        config: SparrowConfig,
    ) -> None:
        self.xb = jnp.asarray(disk_xb, jnp.int32)
        self.y = jnp.asarray(disk_y, jnp.float32)
        self.n, self.d = self.xb.shape
        self.config = config
        if config.sample_size > self.n:
            raise ValueError("sample_size exceeds dataset size")
        # ownership is static per run; feature_mask sits on the
        # per-segment hot path, so build the table once
        self._feat_masks = jnp.asarray(
            feature_ownership_masks(self.d, config.n_workers, config.ownership_redundancy)
        )

    # ----- feature ownership (feature-based parallelization, §4) -----
    def feature_mask(self, worker_id: int) -> jnp.ndarray:
        return self._feat_masks[worker_id]


class SparrowWorker(SparrowWorkerBase):
    """Implements the simulator's TMSNWorker protocol for Sparrow."""

    # ----- protocol hooks -----
    def init_state(self, worker_id: int, seed: int) -> SparrowState:
        key = jax.random.PRNGKey(seed)
        model = empty_model(self.config.capacity)
        disk_margin = jnp.zeros((self.n,), jnp.float32)
        disk_t = jnp.zeros((self.n,), jnp.int32)
        key, sub = jax.random.split(key)
        sample = self._draw_sample(sub, model, disk_margin)
        return SparrowState(
            worker_id=worker_id,
            model=model,
            cert=0.0,  # log Z(H_0) = log 1
            scanner=init_scanner(self.d, self.config.scanner),
            sample=sample,
            disk_margin=disk_margin,
            disk_t=disk_t,
            key=key,
            needs_resample=False,
            pending_cost=0.0,
            fires=0,
            resamples=0,
            sample_model_count=0,
        )

    def _draw_sample(
        self, key: jax.Array, model: StumpModel, disk_margin: jnp.ndarray
    ) -> SampleState:
        return draw_sample(key, self.xb, self.y, model, disk_margin, self.config.sample_size)

    def run_segment(self, state: SparrowState) -> tuple[SparrowState, float, bool]:
        cost = state.pending_cost
        state = state._replace(pending_cost=0.0)
        if state.needs_resample:
            state, c = self._resample(state)
            return state, cost + c, False
        state, c, fired = self._scan_one_chunk(state)
        return state, cost + c, fired

    def _resample(self, state: SparrowState) -> tuple[SparrowState, float]:
        # Refresh disk weights incrementally (Sampler shares the
        # incremental-update bookkeeping with the Scanner).
        delta = predict_margin_delta(state.model, self.xb, state.disk_t)
        disk_margin = state.disk_margin + delta
        evals = float(jnp.sum(jnp.minimum(state.model.count - state.disk_t, state.model.capacity)))
        disk_t = jnp.full_like(state.disk_t, state.model.count)
        key, sub = jax.random.split(state.key)
        sample = self._draw_sample(sub, state.model, disk_margin)
        cost = self.n * self.config.disk_read_cost + STUMP_EVAL_COST * evals
        if self.config.parallel_sampler:
            # the sampler ran on a second core overlapped with scanning;
            # only the uncovered remainder blocks the scanner
            cost = max(cost - state.scan_since_resample, 0.0)
        new_state = state._replace(
            sample=sample,
            disk_margin=disk_margin,
            disk_t=disk_t,
            key=key,
            needs_resample=False,
            scanner=reset_after_fire(state.scanner, True, self.config.scanner)._replace(
                pos=jnp.zeros((), jnp.int32)
            ),
            resamples=state.resamples + 1,
            sample_model_count=int(state.model.count),
            scan_since_resample=0.0,
        )
        return new_state, cost

    def _scan_one_chunk(self, state: SparrowState) -> tuple[SparrowState, float, bool]:
        cfg = self.config
        scanner, sample, info = scan_chunk(
            state.scanner, state.sample, state.model, self.feature_mask(state.worker_id), cfg.scanner
        )
        chunk = min(cfg.scanner.chunk_size, cfg.sample_size)
        cost = chunk * cfg.mem_read_cost + STUMP_EVAL_COST * float(info.stump_evals)
        fired = bool(info.fired)
        state = state._replace(
            scanner=scanner, sample=sample,
            scan_since_resample=state.scan_since_resample + cost,
        )
        if fired:
            # alpha + certificate from the sound lower confidence bound
            # on the edge (>= the tested gamma; see scanner.scan_chunk)
            gamma = jnp.asarray(info.cert_gamma)
            alpha = alpha_from_gamma(gamma)
            model = append_stump(state.model, info.feat, info.thr, info.sign, alpha)
            if int(model.count) == int(state.model.count):
                # at capacity: the strong rule cannot grow — do NOT
                # advance the certificate (it would claim progress the
                # model does not contain)
                return state, cost, False
            cert = state.cert + 0.5 * float(jnp.log1p(-4.0 * float(gamma) ** 2))
            scanner = reset_after_fire(
                scanner, cfg.keep_gamma_on_fire, cfg.scanner, info.emp_gamma
            )
            state = state._replace(
                model=model, cert=cert, scanner=scanner, fires=state.fires + 1
            )
            # ESS check (prose of §3): stale sample -> schedule resample.
            w = jnp.exp(
                jnp.clip(-state.sample.y * (state.sample.margin_l - state.sample.margin_s), -30.0, 30.0)
            )
            ess = float(effective_sample_size(w))
            if ess / cfg.sample_size < cfg.ess_threshold:
                state = state._replace(needs_resample=True)
        elif bool(info.full_pass):
            # Full cycle without firing: halve gamma, clear accumulators
            # (no example double-counted within one "invocation") and
            # KEEP SCANNING. Resampling is driven by the ESS test alone
            # (paper §3); a fruitless pass only means the target edge was
            # too ambitious. Last resort: if gamma has hit the floor and
            # the model has advanced since sampling, draw a fresh sample.
            scanner2 = reset_after_fruitless_pass(state.scanner)
            advanced = int(state.model.count) > state.sample_model_count
            exhausted = float(state.scanner.gamma) <= 2e-4 and advanced
            w = jnp.exp(
                jnp.clip(-state.sample.y * (state.sample.margin_l - state.sample.margin_s), -30.0, 30.0)
            )
            ess = float(effective_sample_size(w))
            stale = ess / self.config.sample_size < self.config.ess_threshold
            state = state._replace(scanner=scanner2, needs_resample=stale or exhausted)
        return state, cost, fired

    def certificate(self, state: SparrowState) -> float:
        return state.cert

    def export_model(self, state: SparrowState) -> StumpModel:
        return state.model

    def payload_bytes(self, model: StumpModel) -> int:
        return model_payload_bytes(model)

    @staticmethod
    def _common_prefix(a: StumpModel, b: StumpModel) -> int:
        """Length of the shared stump prefix (adopted models usually
        extend a common broadcast lineage, so this is long)."""
        n = min(int(a.count), int(b.count))
        if n == 0:
            return 0
        same = (
            (np.asarray(a.feat[:n]) == np.asarray(b.feat[:n]))
            & (np.asarray(a.thr[:n]) == np.asarray(b.thr[:n]))
            & (np.asarray(a.sign[:n]) == np.asarray(b.sign[:n]))
            & (np.asarray(a.alpha[:n]) == np.asarray(b.alpha[:n]))
        )
        bad = np.flatnonzero(~same)
        return int(bad[0]) if bad.size else n

    def adopt(self, state: SparrowState, model: StumpModel, certificate: float) -> SparrowState:
        """Interrupt + replace (H, L).

        Incremental margin transfer (paper §4.1 applied across models):
        adopted models share a long common prefix ``p`` with the local
        lineage, so only the two divergent suffixes are re-evaluated:

            margin_new = margin_old_full - delta_old(p..oc) + delta_new(p..nc)

        Cost is m x (suffix lengths) stump-evals — NOT m x count (a full
        recompute per adoption made 10-worker runs ~10x slower; §Repro).
        """
        oc, nc = int(state.model.count), int(model.count)
        p = self._common_prefix(state.model, model)
        xb = state.sample.xb
        # 1. bring margins current under the OLD model (lazy work due anyway)
        catchup = predict_margin_delta(state.model, xb, state.sample.t_l)
        evals = float(jnp.sum(jnp.clip(state.model.count - state.sample.t_l, 0, None)))
        full_old = state.sample.margin_l + catchup
        # 2. swap the divergent suffixes
        pfx = jnp.full((xb.shape[0],), p, jnp.int32)
        old_sfx = predict_margin_delta(state.model, xb, pfx)
        new_sfx = predict_margin_delta(model, xb, pfx)
        m_new = full_old - old_sfx + new_sfx
        evals += float(xb.shape[0] * ((oc - p) + (nc - p)))
        sample = state.sample._replace(
            # keep margin_s so scan weights stay importance-corrected
            margin_l=m_new,
            t_l=jnp.full_like(state.sample.t_l, model.count),
        )
        # disk bookkeeping: valid iff the divergence is beyond the last
        # disk refresh (disk_t is uniform per resample)
        disk_t0 = int(state.disk_t[0])
        if p >= disk_t0:
            disk_margin, disk_t = state.disk_margin, state.disk_t
        else:
            disk_margin = jnp.zeros_like(state.disk_margin)
            disk_t = jnp.zeros_like(state.disk_t)
        recompute_cost = STUMP_EVAL_COST * evals * self.config.mem_read_cost
        return state._replace(
            model=model,
            cert=float(certificate),
            sample=sample,
            disk_margin=disk_margin,
            disk_t=disk_t,
            scanner=reset_after_fire(state.scanner, True, self.config.scanner),
            pending_cost=state.pending_cost + recompute_cost,
        )
