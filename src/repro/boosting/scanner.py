"""The Scanner (paper §4.1, Algorithm 2).

Reads the in-memory sample cyclically in *chunks* (our interruption /
check granularity — the paper checks the stopping rule per example; a
chunk is the TPU/vector-friendly equivalent and is conservative: we can
only fire later than the paper would, never earlier on less evidence).

Per chunk it:
  1. lazily refreshes example weights (incremental update from each
     example's last-touched stump count ``t_l`` — paper's
     ``UPDATEWEIGHT``),
  2. scatter-adds ``w*y`` into the (feature, bin) histogram,
  3. re-derives every candidate's edge mass and applies the
     iterated-logarithm stopping rule.

State is a pytree; the chunk step is jittable.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.stopping import StoppingRuleParams, stopping_rule_fires
from repro.boosting.stumps import (
    StumpModel,
    edge_histogram,
    edges_from_histogram,
    predict_margin_delta,
)


class ScannerConfig(NamedTuple):
    chunk_size: int = 2048
    num_bins: int = 32
    gamma0: float = 0.25
    #: scan budget per gamma level, as a multiple of the sample size m;
    #: exceeding it halves gamma (Algorithm 2: ``if m > M``).
    budget_mult: float = 4.0
    C: float = 1.0
    delta: float = 1e-6
    #: route histogram accumulation through the Pallas edge_scan kernel
    #: (the one switch for it; interpret mode off the TPU, compiled
    #: Mosaic on it).
    use_kernel: bool = False
    #: gamma policy after a successful fire:
    #:   "keep"  - pseudocode: stay at the collapsed level (tiny alphas),
    #:   "track" - next target = 0.75 x the fired rule's EMPIRICAL edge
    #:             (follows the decaying edge sequence without fruitless
    #:             passes; what the released Sparrow effectively does)
    gamma_policy: str = "track"

    @property
    def rule_params(self) -> StoppingRuleParams:
        return StoppingRuleParams(C=self.C, delta=self.delta)


class ScannerState(NamedTuple):
    hist: jnp.ndarray  # (d, B) f32 accumulated wy histogram
    W: jnp.ndarray  # () f32 total |w| scanned
    V: jnp.ndarray  # () f32 total w^2 scanned
    pos: jnp.ndarray  # () i32 cursor into the sample
    n_scanned: jnp.ndarray  # () i32 examples since last fire/reset
    budget_used: jnp.ndarray  # () i32 examples since gamma level start
    gamma: jnp.ndarray  # () f32 current target edge


class SampleState(NamedTuple):
    """The in-memory sample with lazy-weight bookkeeping (paper's
    per-example tuple ``(x, y, w_s, w_l, H_l)`` in margin form)."""

    xb: jnp.ndarray  # (m, d) i32 binned features
    y: jnp.ndarray  # (m,) f32 labels +-1
    margin_s: jnp.ndarray  # (m,) f32 H(x) at sampling time (w_s = exp(-y*margin_s))
    margin_l: jnp.ndarray  # (m,) f32 latest computed margin
    t_l: jnp.ndarray  # (m,) i32 stump count at latest margin refresh


class FireInfo(NamedTuple):
    fired: jnp.ndarray  # () bool
    feat: jnp.ndarray  # () i32
    thr: jnp.ndarray  # () i32
    sign: jnp.ndarray  # () f32
    gamma: jnp.ndarray  # () f32 certified target edge at fire time
    cert_gamma: jnp.ndarray  # () f32 sound lower confidence bound on the edge
    emp_gamma: jnp.ndarray  # () f32 empirical edge of the fired rule
    full_pass: jnp.ndarray  # () bool — completed a cycle without firing
    stump_evals: jnp.ndarray  # () f32 — incremental-update work done (cost model)


def init_scanner(num_features: int, config: ScannerConfig) -> ScannerState:
    return ScannerState(
        hist=jnp.zeros((num_features, config.num_bins), jnp.float32),
        W=jnp.zeros((), jnp.float32),
        V=jnp.zeros((), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
        n_scanned=jnp.zeros((), jnp.int32),
        budget_used=jnp.zeros((), jnp.int32),
        gamma=jnp.asarray(config.gamma0, jnp.float32),
    )


def reset_after_fire(
    state: ScannerState,
    keep_gamma: bool,
    config: ScannerConfig,
    emp_gamma: jnp.ndarray | float | None = None,
) -> ScannerState:
    """Clear accumulators after a weak rule is added (or adopted)."""
    if not keep_gamma:
        gamma = jnp.asarray(config.gamma0, jnp.float32)
    elif config.gamma_policy == "track" and emp_gamma is not None:
        gamma = jnp.clip(jnp.asarray(emp_gamma) * 0.75, 1e-4, config.gamma0)
    else:
        gamma = state.gamma
    return ScannerState(
        hist=jnp.zeros_like(state.hist),
        W=jnp.zeros_like(state.W),
        V=jnp.zeros_like(state.V),
        pos=state.pos,
        n_scanned=jnp.zeros_like(state.n_scanned),
        budget_used=jnp.zeros_like(state.budget_used),
        gamma=gamma,
    )


def reset_after_fruitless_pass(state: ScannerState) -> ScannerState:
    """A full cycle without firing: the target edge is too ambitious for
    this sample. Halve gamma and clear the accumulators (each scanner
    "invocation" must see each example at most once, or the martingale
    evidence double-counts).

    Deviation from Algorithm 1 (documented in DESIGN.md): the pseudocode
    returns Fail and unconditionally resamples, which deadlocks when the
    model has not changed since sampling (the fresh sample is
    distributionally identical and the scanner fails forever at the same
    gamma). We halve gamma here and let the worker resample only when
    the model advanced since the last sample.
    """
    return ScannerState(
        hist=jnp.zeros_like(state.hist),
        W=jnp.zeros_like(state.W),
        V=jnp.zeros_like(state.V),
        pos=state.pos,
        n_scanned=jnp.zeros_like(state.n_scanned),
        budget_used=jnp.zeros_like(state.budget_used),
        gamma=state.gamma * 0.5,
    )


@functools.partial(jax.jit, static_argnames=("config",))
def scan_chunk(
    scanner: ScannerState,
    sample: SampleState,
    model: StumpModel,
    feat_mask: jnp.ndarray,
    config: ScannerConfig,
) -> tuple[ScannerState, SampleState, FireInfo]:
    """Process one chunk of the in-memory sample.

    Args:
        feat_mask: (d,) bool — features this worker owns (feature-based
            parallelization, paper §4). Candidates on un-owned features
            never fire.
    """
    m = sample.xb.shape[0]
    c = config.chunk_size
    offs = jnp.arange(c, dtype=jnp.int32)
    # Do not scan past a full cycle: mask examples beyond it.
    remaining = jnp.maximum(m - scanner.n_scanned, 0)
    valid = offs < remaining
    idx = (scanner.pos + offs) % m

    xb_c = sample.xb[idx]  # (c, d)
    y_c = sample.y[idx]

    # --- lazy incremental weight refresh (UPDATEWEIGHT) ---
    t_from = sample.t_l[idx]
    delta = predict_margin_delta(model, xb_c, t_from)  # (c,)
    margin_new = sample.margin_l[idx] + delta
    # scan weight = w_latest / w_sampled = exp(-y (H(x) - H_s(x)))
    logw = -y_c * (margin_new - sample.margin_s[idx])
    w = jnp.exp(jnp.clip(logw, -30.0, 30.0)) * valid.astype(jnp.float32)
    stump_evals = jnp.sum(
        jnp.minimum(model.count - t_from, model.capacity) * valid, dtype=jnp.float32
    )

    sample = sample._replace(
        margin_l=sample.margin_l.at[idx].set(
            jnp.where(valid, margin_new, sample.margin_l[idx])
        ),
        t_l=sample.t_l.at[idx].set(jnp.where(valid, model.count, sample.t_l[idx])),
    )

    # --- accumulate histogram + scalars ---
    wy = w * y_c
    if config.use_kernel:
        from repro.kernels import ops as kops

        h_k, W_k, V_k, _ = kops.edge_scan(
            xb_c, wy, w, num_bins=config.num_bins, tile_n=min(c, 512)
        )
        hist = scanner.hist + h_k
        W = scanner.W + W_k
        V = scanner.V + V_k
    else:
        hist = scanner.hist + edge_histogram(xb_c, wy, config.num_bins)
        W = scanner.W + jnp.sum(jnp.abs(w))
        V = scanner.V + jnp.sum(w * w)
    n_new = jnp.sum(valid, dtype=jnp.int32)
    n_scanned = scanner.n_scanned + n_new
    budget_used = scanner.budget_used + n_new

    # --- budget check: halve gamma when the level's budget is exhausted ---
    budget = jnp.asarray(config.budget_mult * m, jnp.int32)
    over = budget_used > budget
    gamma = jnp.where(over, scanner.gamma * 0.5, scanner.gamma)
    budget_used = jnp.where(over, 0, budget_used)

    # --- stopping rule over every candidate ---
    edges = edges_from_histogram(hist)  # (d, B-1)
    fires, signs, rule_score = stopping_rule_fires(edges, W, V, gamma, config.rule_params)
    fires = fires & feat_mask[:, None]
    # pick the strongest firing candidate: largest statistic - threshold
    score = jnp.where(fires, rule_score, -jnp.inf)
    flat = score.ravel()
    best = jnp.argmax(flat)
    fired = jnp.isfinite(flat[best])
    nb = edges.shape[1]
    feat = (best // nb).astype(jnp.int32)
    thr = (best % nb).astype(jnp.int32)
    sign = signs[feat, thr]
    emp_gamma = jnp.abs(edges[feat, thr]) / jnp.maximum(2.0 * W, 1e-9)
    # Sound lower CONFIDENCE bound on the fired rule's edge: the LIL
    # bound |m - mu*W| <= thr holds uniformly in t, so
    #   mu >= (|m| - thr) / W   =>   gamma_lb = (|m| - thr) / (2W)
    # (tighter than the tested target gamma; alpha is set from this).
    M_best = jnp.abs(edges[feat, thr]) - 2.0 * gamma * W
    thr_best = M_best - rule_score[feat, thr]  # threshold at fire time
    cert_gamma = (jnp.abs(edges[feat, thr]) - thr_best) / jnp.maximum(2.0 * W, 1e-9)
    cert_gamma = jnp.clip(cert_gamma, gamma, 0.49)

    full_pass = (~fired) & (n_scanned >= m)

    new_scanner = ScannerState(
        hist=hist,
        W=W,
        V=V,
        pos=(scanner.pos + n_new) % m,
        n_scanned=n_scanned,
        budget_used=budget_used,
        gamma=gamma,
    )
    info = FireInfo(
        fired=fired,
        feat=feat,
        thr=thr,
        sign=sign,
        gamma=gamma,
        cert_gamma=cert_gamma,
        emp_gamma=emp_gamma,
        full_pass=full_pass,
        stump_evals=stump_evals,
    )
    return new_scanner, sample, info
