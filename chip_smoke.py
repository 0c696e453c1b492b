#!/usr/bin/env python3
"""Smoke run of the Sparrow TMSN round engine on TPU chips.

    python chip_smoke.py             # one chip: kernels, data, worker, engine
    python chip_smoke.py --chips 4   # the sharded engine on a 4-chip mesh,
                                     # compared with the one-chip engine

One process drives the paper's workload through its normal entry points:
``make_splice_like`` data (8M examples, d=64, 8 bins), a
``BatchedSparrowWorker`` cohort of 16 workers whose chunk scan runs the
compiled ``edge_scan`` kernel, and ``make_engine`` with pending queues,
whose delivery runs the compiled ``round_step`` kernel. It then checks
the results: compiled (not interpreted) kernels, a real-size histogram
chunk against the float32 reference, finite and monotone certificates
that improved, and a best model that beats the base rate on held-out
data. Any failed check exits nonzero and prints no result.

Timings are printed for orientation only: this is a smoke, not a
benchmark. The last line of standard output is the JSON result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

There is no CPU fallback: without a TPU it exits nonzero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


class SmokeSize(NamedTuple):
    """The smoke's configuration. The paper's 50M examples are cut to 8M
    so the int32 bins plus every worker's disk state fit one 16 GB chip
    with room for compile temporaries; d, B and the class balance are
    ``SpliceConfig``'s own."""

    n: int = 8_000_000
    d: int = 64
    num_bins: int = 8
    n_workers: int = 16
    sample_size: int = 200_000
    capacity: int = 256
    chunk_size: int = 2048
    inflight_capacity: int = 64
    rounds: int = 64


#: max |kernel - reference| over a histogram chunk, relative to the
#: chunk's largest reference cell (a float32 sum reordered; a bf16
#: contraction would miss by ~1e-3)
HIST_RTOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects failed checks; the run fails if any did."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        log(f"  [{'ok' if ok else 'FAILED'}] {what}")
        if not ok:
            self.failed.append(what)


def kernel_phase(size: SmokeSize, checks: Checks) -> None:
    """The kernels compile to Mosaic at the run's shapes, and one
    real-size ``edge_scan`` chunk matches the float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.boosting.stumps import edge_histogram
    from repro.kernels import ops
    from repro.kernels.platform import resolve_interpret

    interpret = resolve_interpret(None)
    log(f"kernels: interpret resolves to {interpret}")
    checks.expect(interpret is False, "kernels run compiled, not interpreted")

    w, c, d, b = size.n_workers, size.chunk_size, size.d, size.num_bins
    cap = size.inflight_capacity
    sds = jax.ShapeDtypeStruct
    lowered = {
        "edge_scan_batched": jax.jit(
            lambda x, wy, wt: ops.edge_scan_batched(x, wy, wt, num_bins=b, tile_n=min(c, 512))
        ).lower(sds((w, c, d), jnp.int32), sds((w, c), jnp.float32), sds((w, c), jnp.float32)),
        "round_deliver": jax.jit(
            lambda qc, qd, qs, ql, c0, al, cr, sp, r: ops.round_deliver(
                qc, qd, qs, ql, c0, al, cr, sp, r, eps=0.0
            )
        ).lower(
            sds((w, cap), jnp.float32),
            sds((w, cap), jnp.int32),
            sds((w, cap), jnp.int32),
            sds((w, cap), jnp.int32),
            sds((w,), jnp.float32),
            sds((w,), jnp.bool_),
            sds((w,), jnp.float32),
            sds((w,), jnp.float32),
            sds((), jnp.int32),
        ),
    }
    for name, low in lowered.items():
        text = low.compile().as_text()
        checks.expect("tpu_custom_call" in text, f"{name} compiles to a tpu_custom_call")

    # one real-size chunk: splice-shaped bins, heavy-tailed signed weights
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    xb = jax.random.randint(k1, (c, d), 0, b, dtype=jnp.int32)
    wt = jnp.exp(jax.random.normal(k2, (c,)))
    y = jnp.where(jax.random.bernoulli(k3, 0.3, (c,)), 1.0, -1.0)
    wy = wt * y
    hist, big_w, big_v, big_t = ops.edge_scan(xb, wy, wt, num_bins=b, tile_n=min(c, 512))
    ref = edge_histogram(xb, wy, b)
    hist, ref = np.asarray(hist), np.asarray(ref)
    err = float(np.max(np.abs(hist - ref)) / np.max(np.abs(ref)))
    log(f"kernels: edge_scan chunk ({c}, {d}) x B={b}: max error {err:.3e} of the largest cell")
    checks.expect(err <= HIST_RTOL, f"edge_scan histogram within {HIST_RTOL:g} of float32 reference")
    wt64 = np.asarray(wt, np.float64)
    scal_ok = np.allclose(
        [float(big_w), float(big_v), float(big_t)],
        [np.sum(wt64), np.sum(wt64 * wt64), np.sum(np.asarray(wy, np.float64))],
        rtol=1e-5,
        atol=1e-3,
    )
    checks.expect(bool(scal_ok), "edge_scan W, V, T match float64 sums")


def make_data(size: SmokeSize):
    """``(train bins, train labels, test bins, test labels)``, generated
    on the device from seed 0; the unsplit arrays are freed."""
    import jax

    from repro.data.splice import SpliceConfig, make_splice_like, train_test_split

    t0 = time.perf_counter()
    xb, y, _ = jax.block_until_ready(
        make_splice_like(SpliceConfig(n=size.n, d=size.d, num_bins=size.num_bins, seed=0))
    )
    t1 = time.perf_counter()
    xtr, ytr, xte, yte = jax.block_until_ready(train_test_split(xb, y))
    del xb, y
    log(
        f"data: {size.n} examples, d={size.d}, B={size.num_bins}; train {xtr.shape[0]}, "
        f"test {xte.shape[0]} (generate {t1 - t0:.2f} s, split {time.perf_counter() - t1:.2f} s)"
    )
    return xtr, ytr, xte, yte


def make_worker(size: SmokeSize, xtr, ytr):
    from repro.boosting import BatchedSparrowWorker, SparrowConfig
    from repro.boosting.scanner import ScannerConfig

    return BatchedSparrowWorker(
        xtr,
        ytr,
        SparrowConfig(
            sample_size=size.sample_size,
            capacity=size.capacity,
            n_workers=size.n_workers,
            scanner=ScannerConfig(
                chunk_size=size.chunk_size, num_bins=size.num_bins, use_kernel=True
            ),
        ),
    )


def engine_config(size: SmokeSize, mesh=None):
    from repro.core.engine import EngineConfig

    return EngineConfig(
        n_workers=size.n_workers,
        inflight_capacity=size.inflight_capacity,
        round_step_impl="pallas",
        max_rounds=size.rounds,
        seed=0,
        mesh=mesh,
    )


def check_run(res, checks: Checks, label: str) -> None:
    """Certificates finite, non-increasing per worker, and improved."""
    import numpy as np

    per_worker: dict[int, list[float]] = {}
    for _, wid, cert in res.history:
        per_worker.setdefault(wid, []).append(cert)
    initial = min(c[0] for c in per_worker.values())
    final = np.asarray(res.final_certificates)
    monotone = all(
        all(b <= a for a, b in zip(c, c[1:])) for c in per_worker.values()
    )
    finite = bool(np.all(np.isfinite(final))) and all(
        np.all(np.isfinite(c)) for c in per_worker.values()
    )
    log(
        f"{label}: {res.rounds} rounds, best certificate {final.min():.6f} "
        f"(initial {initial:.6f}), {res.messages_accepted} adoptions"
    )
    checks.expect(finite, f"{label}: every certificate is finite")
    checks.expect(monotone, f"{label}: every worker's certificate is non-increasing")
    checks.expect(float(final.min()) < initial, f"{label}: best certificate improved")


def timed_run(engine):
    """``engine.run()`` on the host clock, until its models are ready."""
    import jax

    t0 = time.perf_counter()
    res = engine.run()
    jax.block_until_ready(res.final_models)
    return res, time.perf_counter() - t0


def single_chip_phase(size: SmokeSize, checks: Checks) -> None:
    import jax
    import numpy as np

    from repro.boosting.stumps import error_rate
    from repro.core.engine import make_engine

    xtr, ytr, xte, yte = make_data(size)
    worker = make_worker(size, xtr, ytr)
    engine = make_engine(worker, engine_config(size))
    res, cold = timed_run(engine)
    res2, warm = timed_run(engine)
    log(
        f"engine (smoke timing, not a benchmark): first run {cold:.2f} s "
        f"(compile + run), second run {warm:.2f} s "
        f"({1e3 * warm / max(res2.rounds, 1):.2f} ms/round); compile ~{cold - warm:.2f} s"
    )
    check_run(res, checks, "engine")
    checks.expect(
        res2.final_certificates == res.final_certificates,
        "engine: a second run reproduces the first",
    )

    best = int(np.argmin(res.final_certificates))
    model = res.final_models[best]
    err = float(jax.jit(error_rate)(model, xte, yte))
    pos = float(np.mean(np.asarray(yte) > 0))
    base = min(pos, 1.0 - pos)
    log(
        f"model: worker {best}, {int(model.count)} stumps, test error {err:.4f} "
        f"vs base rate {base:.4f} (positive fraction {pos:.4f})"
    )
    checks.expect(err < base, "best model's test error is below the base rate")


def first_parting_round(engines, rounds: int, chunk: int) -> int | None:
    """Replay the engines side by side, ``chunk`` rounds per dispatch,
    and return the first round (1-based) whose certificates differ."""
    import numpy as np

    states = [e._init_state() for e in engines]
    done = 0
    while done < rounds:
        k = min(chunk, rounds - done)
        outs = [e._chunk_fn(k, s)(s) for e, s in zip(engines, states)]
        states = [s for s, _ in outs]
        certs = [np.asarray(info.certs) for _, info in outs]
        differ = np.any(certs[0] != certs[1], axis=1)
        if differ.any():
            return done + int(np.argmax(differ)) + 1
        done += k
    return None


def four_chip_phase(size: SmokeSize, checks: Checks, chips: int) -> None:
    """The sharded engine (4 workers per chip, the disk data replicated
    per chip) against the one-chip engine on the same cohort."""
    import numpy as np

    from repro.core.engine import make_engine
    from repro.launch.mesh import make_worker_mesh

    xtr, ytr, _, _ = make_data(size)
    worker = make_worker(size, xtr, ytr)
    sharded = make_engine(worker, engine_config(size, mesh=make_worker_mesh(chips)))
    single = make_engine(worker, engine_config(size))
    log(f"engines: {type(sharded).__name__} on {chips} chips vs {type(single).__name__}")
    res_s, t_s = timed_run(sharded)
    check_run(res_s, checks, f"sharded ({chips} chips)")
    res_1, t_1 = timed_run(single)
    check_run(res_1, checks, "single chip")
    log(f"engines (smoke timing, incl. compile): sharded {t_s:.2f} s, single {t_1:.2f} s")
    a = np.asarray(res_s.final_certificates)
    b = np.asarray(res_1.final_certificates)
    same = bool(np.array_equal(a, b))
    log(f"compare: final certificates identical: {same} (max |diff| {np.max(np.abs(a - b)):.3e})")
    if not same:
        parted = first_parting_round(
            [sharded, single], size.rounds, sharded.config.rounds_per_dispatch
        )
        log(f"compare: first round where the runs part: {parted}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        default=1,
        choices=(1, 4),
        help="4: run only the sharded engine on a 4-chip mesh and the one-chip "
        "engine it is compared with",
    )
    args = ap.parse_args()

    import jax

    from repro.launch.runtime import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(jax.devices())} visible", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    log(f"cache: {cache} ({'warm' if warm else 'cold'} at start)")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    size = SmokeSize()
    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        kernel_phase(size, checks)
        single_chip_phase(size, checks)
    else:
        four_chip_phase(size, checks, args.chips)
    stats = dev.memory_stats() or {}
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"total: {time.perf_counter() - t0:.2f} s")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed", file=sys.stderr)
        return 1
    result = {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
