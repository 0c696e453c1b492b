"""Plain reference of a Sparrow TMSN training, for the `correct` check.

A straightforward implementation of the semantics the engine runs: W
Sparrow workers (paper §4, Algorithms 1 and 2), each owning the
features ``j`` with ``j mod W == i``, scanning its in-memory sample one
chunk per round with the iterated-logarithm stopping rule, drawing
samples from the disk set by minimal-variance sampling, and gossiping
certified improvements (paper §4.2): a worker whose certificate
improved sends (certificate, model) to every other worker, the message
arrives one round later, and a receiver adopts the best arrival that
beats its own certificate by more than ``eps``.

It imports nothing of the program under test. The histogram is a plain
one-hot sum in float32 (no kernel, no matmul), a disk pass sums the
margins stump by stump, delivery is a dense argmin on the host, and
there is no chunked dispatch, pending queue or sharding. With
``low_precision`` the scanned weights and the statistics the stopping
rule reads (histogram, W, V) are held in bfloat16, the sums still taken
in float32: the control that the comparison has to reject.

The reference follows one training round by round on the host and
records what a user of the program can observe: the certificate history,
every worker's final model, the adoptions and the round the target was
reached. Float results match the program's to rounding only, so a
resample drawn after a fire picks rows by weights that differ in their
last bits; ``exact_until`` marks the first round at which that happened,
and the comparison stops there.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: relative cost of one incremental stump evaluation against one
#: example read (the cost model behind the engine's per-worker clock)
STUMP_EVAL_COST = 0.1


class Params(NamedTuple):
    """The static settings one training depends on."""

    n_workers: int
    sample_size: int
    capacity: int
    chunk_size: int
    num_bins: int
    gamma0: float
    budget_mult: float
    C: float
    delta: float
    ess_threshold: float
    mem_read_cost: float
    disk_read_cost: float
    eps: float
    low_precision: bool = False


def params_from_config(cfg: dict, low_precision: bool = False) -> Params:
    s, sc, e = cfg["sparrow"], cfg["scanner"], cfg["engine"]
    return Params(
        n_workers=int(e["n_workers"]),
        sample_size=int(s["sample_size"]),
        capacity=int(s["capacity"]),
        chunk_size=int(sc["chunk_size"]),
        num_bins=int(cfg["data"]["num_bins"]),
        gamma0=float(sc["gamma0"]),
        budget_mult=float(sc["budget_mult"]),
        C=float(sc["C"]),
        delta=float(sc["delta"]),
        ess_threshold=float(s["ess_threshold"]),
        mem_read_cost=float(s["mem_read_cost"]),
        disk_read_cost=float(s["disk_read_cost"]),
        eps=float(e["eps"]),
        low_precision=low_precision,
    )


# ----------------------------------------------------------------------
# one worker's arithmetic
# ----------------------------------------------------------------------


def margin_delta(model: dict, xb: jnp.ndarray, t_from: jnp.ndarray) -> jnp.ndarray:
    """``H_count(x) - H_{t_from}(x)`` per row: the stumps in slots
    ``t_from .. count`` of each row, all evaluated at once."""
    pred = jnp.where(xb[:, model["feat"]] > model["thr"][None, :], 1.0, -1.0)
    slot = jnp.arange(model["feat"].shape[0])[None, :]
    live = (slot >= t_from[:, None]) & (slot < model["count"])
    return jnp.sum(pred * model["sign"][None, :] * model["alpha"][None, :] * live, axis=1)


def disk_margin_delta(model: dict, xb: jnp.ndarray, t_from) -> jnp.ndarray:
    """:func:`margin_delta` over the whole disk set from one stump count,
    stump by stump, so no (rows, stumps) array is ever held."""

    def add(k, acc):
        pred = jnp.where(xb[:, model["feat"][k]] > model["thr"][k], 1.0, -1.0)
        return acc + model["alpha"][k] * (model["sign"][k] * pred)

    return jax.lax.fori_loop(t_from, model["count"], add, jnp.zeros(xb.shape[0], jnp.float32))


def mv_sample(key: jax.Array, w: jnp.ndarray, m: int) -> jnp.ndarray:
    """Minimal-variance (systematic) resampling: ``m`` row indices with
    probability proportional to ``w`` and one uniform offset."""
    w = jnp.maximum(w, 0.0)
    total = jnp.sum(w)
    p = jnp.where(total > 0, w / jnp.maximum(total, 1e-30), 1.0 / w.shape[0])
    cum = jnp.cumsum(p)
    points = (jnp.arange(m, dtype=jnp.float32) + jax.random.uniform(key)) / m
    idx = jnp.searchsorted(cum, points, side="left")
    return jnp.clip(idx, 0, w.shape[0] - 1).astype(jnp.int32)


def draw(key, xb, y, disk_margin, count, m):
    """A fresh sample from the disk set, weighted by ``exp(-y H)``."""
    w = jnp.exp(jnp.clip(-y * disk_margin, -30.0, 30.0))
    idx = mv_sample(key, w, m)
    margin = disk_margin[idx]
    return {
        "xb": xb[idx],
        "y": y[idx],
        "margin_s": margin,
        "margin_l": margin,
        "t_l": jnp.full((m,), count, jnp.int32),
    }


def lil_threshold(V, M, p: Params):
    """Iterated-logarithm stopping threshold (paper Theorem 1)."""
    M = jnp.abs(M)
    ratio = jnp.maximum(V / jnp.maximum(M, 1.0), jnp.e)
    inner = V * (jnp.maximum(jnp.log(jnp.log(ratio)), 0.0) + jnp.log(1.0 / p.delta))
    return jnp.where(V > 0, p.C * jnp.sqrt(jnp.maximum(inner, 0.0)), jnp.inf)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def clear_scanner(sc: dict, gamma) -> dict:
    z = jnp.zeros((), jnp.float32)
    zi = jnp.zeros((), jnp.int32)
    return dict(sc, hist=jnp.zeros_like(sc["hist"]), W=z, V=z, n_scanned=zi, budget_used=zi, gamma=gamma)


def scan_one(st: dict, fmask: jnp.ndarray, active: jnp.ndarray, p: Params):
    """One scanned chunk of one worker; returns (state, cost, fired)."""
    m, c, T = p.sample_size, p.chunk_size, p.capacity
    sc, smp, model = st["scanner"], st["sample"], st["model"]
    offs = jnp.arange(c, dtype=jnp.int32)
    valid = offs < jnp.maximum(m - sc["n_scanned"], 0)
    idx = (sc["pos"] + offs) % m
    xb_c, y_c, t_from = smp["xb"][idx], smp["y"][idx], smp["t_l"][idx]
    margin_new = smp["margin_l"][idx] + margin_delta(model, xb_c, t_from)
    w = jnp.exp(jnp.clip(-y_c * (margin_new - smp["margin_s"][idx]), -30.0, 30.0))
    w = w * valid.astype(jnp.float32)
    evals = jnp.sum(jnp.minimum(model["count"] - t_from, T) * valid, dtype=jnp.float32)
    smp = dict(
        smp,
        margin_l=smp["margin_l"].at[idx].set(jnp.where(valid, margin_new, smp["margin_l"][idx])),
        t_l=smp["t_l"].at[idx].set(jnp.where(valid, model["count"], smp["t_l"][idx])),
    )
    wy = w * y_c
    if p.low_precision:
        wy, w = _bf16(wy), _bf16(w)
    onehot = (xb_c[:, :, None] == jnp.arange(p.num_bins)[None, None, :]).astype(jnp.float32)
    hist = sc["hist"] + jnp.sum(onehot * wy[:, None, None], axis=0)
    W = sc["W"] + jnp.sum(jnp.abs(w))
    V = sc["V"] + jnp.sum(w * w)
    if p.low_precision:
        hist, W, V = _bf16(hist), _bf16(W), _bf16(V)
    n_new = jnp.sum(valid, dtype=jnp.int32)
    n_scanned = sc["n_scanned"] + n_new
    budget_used = sc["budget_used"] + n_new
    over = budget_used > jnp.asarray(p.budget_mult * m, jnp.int32)
    gamma = jnp.where(over, sc["gamma"] * 0.5, sc["gamma"])
    budget_used = jnp.where(over, 0, budget_used)

    # every candidate stump (feature j, threshold t): edge mass over bins > t
    total = jnp.sum(hist, axis=1, keepdims=True)
    edges = 2.0 * jnp.cumsum(hist[:, ::-1], axis=1)[:, ::-1][:, 1:] - total
    gw = 2.0 * gamma * W
    mp, mn = edges - gw, -edges - gw
    sp, sn = mp - lil_threshold(V, mp, p), mn - lil_threshold(V, mn, p)
    fires = ((sp > 0) | (sn > 0)) & fmask[:, None]
    use_p = sp >= sn
    signs = jnp.where(use_p, 1.0, -1.0)
    score = jnp.where(use_p, sp, sn)
    flat = jnp.where(fires, score, -jnp.inf).ravel()
    best = jnp.argmax(flat)
    hit = jnp.isfinite(flat[best])
    nb = edges.shape[1]
    feat, thr = (best // nb).astype(jnp.int32), (best % nb).astype(jnp.int32)
    e = jnp.abs(edges[feat, thr])
    emp_gamma = e / jnp.maximum(2.0 * W, 1e-9)
    thr_at_fire = (e - 2.0 * gamma * W) - score[feat, thr]
    cert_gamma = jnp.clip((e - thr_at_fire) / jnp.maximum(2.0 * W, 1e-9), gamma, 0.49)
    full_pass = (~hit) & (n_scanned >= m)

    scanned = dict(sc, hist=hist, W=W, V=V, pos=(sc["pos"] + n_new) % m,
                   n_scanned=n_scanned, budget_used=budget_used, gamma=gamma)
    grew = model["count"] < T
    fired = hit & active & grew
    g = jnp.clip(cert_gamma, -0.49, 0.49)
    alpha = 0.5 * jnp.log((0.5 + g) / (0.5 - g))
    k = jnp.minimum(model["count"], T - 1)
    grown = {
        "feat": model["feat"].at[k].set(feat),
        "thr": model["thr"].at[k].set(thr),
        "sign": model["sign"].at[k].set(signs[feat, thr]),
        "alpha": model["alpha"].at[k].set(alpha),
        "count": model["count"] + 1,
    }
    fruitless = (~hit) & full_pass & active
    after_fire = clear_scanner(scanned, jnp.clip(emp_gamma * 0.75, 1e-4, p.gamma0))
    after_pass = clear_scanner(scanned, scanned["gamma"] * 0.5)

    wts = jnp.exp(jnp.clip(-smp["y"] * (smp["margin_l"] - smp["margin_s"]), -30.0, 30.0))
    s1, s2 = jnp.sum(wts), jnp.sum(wts * wts)
    ess = jnp.where(s2 > 0, s1 * s1 / jnp.maximum(s2, 1e-30), 0.0)
    stale = ess / m < p.ess_threshold
    exhausted = (scanned["gamma"] <= 2e-4) & (model["count"] > st["sample_model_count"])
    cost = (min(c, m) * p.mem_read_cost + STUMP_EVAL_COST * evals) * active.astype(jnp.float32)

    pick = lambda a, b: jax.tree_util.tree_map(lambda u, v: jnp.where(fired, u, v), a, b)
    new = dict(
        st,
        model=pick(grown, model),
        cert=jnp.where(fired, st["cert"] + 0.5 * jnp.log1p(-4.0 * jnp.square(cert_gamma)), st["cert"]),
        scanner=pick(after_fire, jax.tree_util.tree_map(
            lambda u, v: jnp.where(fruitless, u, v), after_pass, scanned)),
        sample=smp,
        needs_resample=jnp.where(fired, stale, jnp.where(fruitless, stale | exhausted, st["needs_resample"])),
        scan_since_resample=st["scan_since_resample"] + cost,
    )
    new = jax.tree_util.tree_map(lambda u, v: jnp.where(active, u, v), new, st)
    return new, cost, fired


def resample_one(st: dict, xb, y, p: Params):
    """A fresh sample for one worker: refresh its disk margins, draw."""
    model = st["model"]
    n = xb.shape[0]
    t_from = jnp.maximum(st["disk_t"], 0)
    base = jnp.where(st["disk_t"] < 0, 0.0, st["disk_margin"])
    disk_margin = base + disk_margin_delta(model, xb, t_from)
    evals = (n * jnp.minimum(model["count"] - t_from, p.capacity)).astype(jnp.float32)
    key, sub = jax.random.split(st["key"])
    sample = draw(sub, xb, y, disk_margin, model["count"], p.sample_size)
    sc = clear_scanner(st["scanner"], st["scanner"]["gamma"])
    sc["pos"] = jnp.zeros((), jnp.int32)
    new = dict(st, sample=sample, disk_margin=disk_margin, disk_t=model["count"], key=key,
               needs_resample=jnp.zeros((), bool), scanner=sc,
               sample_model_count=model["count"], scan_since_resample=jnp.zeros((), jnp.float32))
    return new, jnp.asarray(n * p.disk_read_cost + STUMP_EVAL_COST * evals, jnp.float32)


def common_prefix(a: dict, b: dict) -> jnp.ndarray:
    same = (a["feat"] == b["feat"]) & (a["thr"] == b["thr"]) & (a["sign"] == b["sign"]) & (a["alpha"] == b["alpha"])
    same = same & (jnp.arange(a["feat"].shape[0]) < jnp.minimum(a["count"], b["count"]))
    return jnp.sum(jnp.cumprod(same.astype(jnp.int32))).astype(jnp.int32)


def adopt_one(st: dict, new_model: dict, new_cert, p: Params):
    """Replace a worker's model by a received one (paper §4.2) and carry
    its sample's margins over the shared stump prefix."""
    m = p.sample_size
    model, smp = st["model"], st["sample"]
    pre = common_prefix(model, new_model)
    xb = smp["xb"]
    full_old = smp["margin_l"] + margin_delta(model, xb, smp["t_l"])
    evals = jnp.sum(jnp.clip(model["count"] - smp["t_l"], 0, None)).astype(jnp.float32)
    pfx = jnp.full((m,), pre, jnp.int32)
    margin = full_old - margin_delta(model, xb, pfx) + margin_delta(new_model, xb, pfx)
    evals += (m * ((model["count"] - pre) + (new_model["count"] - pre))).astype(jnp.float32)
    new = dict(
        st,
        model=new_model,
        cert=jnp.asarray(new_cert, jnp.float32),
        sample=dict(smp, margin_l=margin, t_l=jnp.full((m,), new_model["count"], jnp.int32)),
        disk_t=jnp.where(pre >= st["disk_t"], st["disk_t"], -1),
        scanner=clear_scanner(st["scanner"], st["scanner"]["gamma"]),
    )
    return new, STUMP_EVAL_COST * evals * p.mem_read_cost


# ----------------------------------------------------------------------
# the cohort
# ----------------------------------------------------------------------


def init_workers(xb, y, d: int, seed: int, p: Params) -> dict:
    """Every worker's first state: an empty model and a uniform sample
    drawn with the key stream ``PRNGKey(seed + 1000 i)``."""
    w, m, T = p.n_workers, p.sample_size, p.capacity
    keys = jnp.stack([jax.random.PRNGKey(seed + 1000 * i) for i in range(w)])
    n = xb.shape[0]

    def one(key):
        key, sub = jax.random.split(key)
        return draw(sub, xb, y, jnp.zeros((n,), jnp.float32), 0, m), key

    # op by op, as the engine's own first draw runs: the sampled rows
    # depend on the last bits of a 7.2M-long float32 cumsum
    sample, keys = jax.vmap(one)(keys)
    zi = lambda: jnp.zeros((w,), jnp.int32)  # a buffer of its own per leaf (donated)
    zf = lambda: jnp.zeros((w,), jnp.float32)
    owner = np.arange(d)[None, :] % w == np.arange(w)[:, None]
    sample = jax.tree_util.tree_map(jnp.copy, sample)  # margin_s and margin_l: two buffers
    return {
        "model": {
            "feat": jnp.zeros((w, T), jnp.int32),
            "thr": jnp.zeros((w, T), jnp.int32),
            "sign": jnp.ones((w, T), jnp.float32),
            "alpha": jnp.zeros((w, T), jnp.float32),
            "count": zi(),
        },
        "cert": zf(),
        "scanner": {
            "hist": jnp.zeros((w, d, p.num_bins), jnp.float32),
            "W": zf(), "V": zf(), "pos": zi(), "n_scanned": zi(), "budget_used": zi(),
            "gamma": jnp.full((w,), p.gamma0, jnp.float32),
        },
        "sample": sample,
        "disk_margin": jnp.zeros((w, n), jnp.float32),
        "disk_t": zi(),
        "key": keys,
        "needs_resample": jnp.zeros((w,), bool),
        "sample_model_count": zi(),
        "scan_since_resample": zf(),
        "feat_mask": jnp.asarray(owner),
    }


def _row(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _set_row(tree, i, row):
    return jax.tree_util.tree_map(lambda a, v: a.at[i].set(v), tree, row)


@functools.partial(jax.jit, static_argnames=("p",), donate_argnums=(0,))
def _scan_all(states, active, p: Params):
    body = lambda st, act: scan_one(st, st["feat_mask"], act, p)
    return jax.vmap(body)(states, active)


@functools.partial(jax.jit, static_argnames=("p",), donate_argnums=(0,))
def _resample_row(states, i, xb, y, p: Params):
    new, cost = resample_one(_row(states, i), xb, y, p)
    return _set_row(states, i, new), cost


@functools.partial(jax.jit, static_argnames=("p",), donate_argnums=(0,))
def _adopt_row(states, i, model, cert, p: Params):
    new, cost = adopt_one(_row(states, i), model, cert, p)
    return _set_row(states, i, new), cost


def train(xb, y, seed: int, p: Params, *, max_rounds: int, target: float | None) -> dict[str, Any]:
    """Follow one training from its first round; returns what a user of
    the engine observes, as host arrays."""
    w = p.n_workers
    d = int(xb.shape[1])
    states = init_workers(xb, y, d, seed, p)
    certs = np.zeros(w, np.float32)
    clock = np.zeros(w, np.float32)
    history = [(0.0, i, 0.0) for i in range(w)]
    history_rounds = [-1] * w
    inbox: tuple[np.ndarray, dict] | None = None  # (sent certs, sent models) of last round
    fired_once = False
    exact_until = None
    accepted = 0
    rounds = 0
    hit_target = target is None
    for r in range(max_rounds):
        take = np.zeros(w, bool)
        cost = np.zeros(w, np.float32)
        if inbox is not None:
            sent, models = inbox
            for dst in range(w):
                offers = np.where(np.arange(w) == dst, np.inf, sent)
                src = int(np.argmin(offers))
                if np.isfinite(offers[src]) and offers[src] < certs[dst] - np.float32(p.eps):
                    take[dst] = True
                    states, c = _adopt_row(states, dst, _row(models, src), offers[src], p)
                    certs[dst] = offers[src]
                    cost[dst] += np.float32(c)
        accepted += int(take.sum())
        need = np.asarray(states["needs_resample"])
        for i in np.flatnonzero(need):
            if fired_once and exact_until is None:
                exact_until = r
            states, c = _resample_row(states, int(i), xb, y, p)
            cost[i] += np.float32(c)
        certs_pre = np.asarray(states["cert"])
        states, scan_cost, fired = _scan_all(states, jnp.asarray(~need), p)
        certs = np.array(states["cert"])
        fired = np.asarray(fired)
        cost = cost + np.asarray(scan_cost)
        clock = clock + cost
        improved = fired & (certs < certs_pre) & ~need
        fired_once = fired_once or bool(fired.any())
        for i in np.flatnonzero(take | improved):
            history.append((float(clock[i]), int(i), float(certs[i])))
            history_rounds.append(r)
        inbox = None
        if improved.any():
            inbox = (np.where(improved, certs, np.inf).astype(np.float32),
                     jax.tree_util.tree_map(jnp.copy, states["model"]))
        rounds = r + 1
        if target is not None and bool(np.any(certs <= np.float32(target))):
            hit_target = True
            break
    model = jax.tree_util.tree_map(np.asarray, states["model"])
    return {
        "rounds": rounds,
        "hit_target": hit_target,
        "history": history,
        "history_rounds": history_rounds,
        "final_certificates": certs.astype(np.float64),
        "models": model,
        "accepted": accepted,
        "exact_until": exact_until,
    }
