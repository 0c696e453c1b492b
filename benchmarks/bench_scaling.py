"""Worker-count scaling of the round-based TMSN engine (the paper's
headline regime: hundreds of independent machines).

Sweeps W ∈ {8, 32, 128, 256} (quick profile stops at 128) and reports,
per W:

  * ``rounds_to_target``   — gossip efficiency (should NOT grow with W;
    more workers means more parallel exploration of the feature space),
  * ``wall_ms_per_round``  — engine throughput: one round advances all W
    workers one segment inside a single jitted computation, so this
    should grow far sublinearly in W,
  * ``per_segment_us``     — wall per worker-segment (the number that
    collapses for the event-driven simulator past ~16 workers).

At W=8 the event simulator runs the same workload for a direct
per-segment speedup ratio (`engine_speedup_vs_sim`).

The *dispatch* section reruns W=128 with
``rounds_per_dispatch ∈ {1, 8, 32}``: one jitted ``lax.scan`` chunk per
dispatch instead of one Python dispatch + host sync per round — the
wall/round at chunk 1 vs 8 is the measured dispatch overhead.

The *sharded* section sweeps W ∈ {64, 256, 1024} through the
shard-mapped engine on 8 forced host devices (each sweep point is a
subprocess so ``XLA_FLAGS=--xla_force_host_platform_device_count`` is
set before the child's first jax import) and reports per-round wall
clock plus gossip bytes/round — the all_gather footprint that would hit
a real interconnect (plus a derived lower-bound ICI-link wire time).
W ∈ {256, 1024} additionally run with ``gossip_mode="gated"``: payloads
move only for each device's top-k improved candidates, and the parent
checks the final certificates stay IDENTICAL to dense (uniform delay)
while gossip bytes/round collapse. It measures substrate throughput and
traffic, not convergence: at W > d some workers own no features (the
paper regime d >= W is what the single-device sweep above covers).

The *sparse* section reruns the sharded sweep with
``inflight_capacity=64``: bounded per-destination pending queues plus
the fused ``kernels/round_step.py`` delivery kernel instead of the dense
``(W_local, W, D)`` in-flight buffer. At uniform delay the end state
must stay digest-identical to dense (worst-first eviction preserves the
per-round delivery argmin), and the wall/round is reported against both
the committed baseline and the same-run dense number — on the Sparrow
workload that wall is worker-compute-bound (per_segment_us is flat
across W), so the representation barely moves it. The wall-time claim
therefore gets its own *round-machinery isolation* pair: a
trivial-segment worker (``_RoundOnlyWorker``) at delay depth 256, where
the dense per-shard ``(W/n_dev, W, 256)`` buffer shift IS the per-round
cost, run dense-vs-sparse on the same profile in the same bench — the
sparse queue must be >= 2x faster per round (measured ~12x on an 8-way
CPU host) or the bench fails loudly. A heterogeneous
delay profile (``het32``: frozen link delays in [1, 32]) then measures
the small-capacity approximation gap dense-vs-sparse — reported, never
assumed away. Finally W=4096 with ``het64`` delays runs BOTH paths
under a hard address-space cap (RLIMIT_AS): the dense buffer alone
(512 x 4096 x 64 f32 per shard, plus its shift copy) exceeds the cap,
so dense must die while sparse completes (dense's in-flight state is a
single 4 GiB allocation before its shift copy; sparse peaks well under
the cap) — the bench fails loudly if dense unexpectedly fits. A
roofline accounting of the fused kernel
(launch/hlo_analysis.round_step_roofline) closes the section.

The *control-plane* section sweeps W ∈ {4096, 10240} (toy worker,
gated gossip, capacity 64, uniform delay, the same 9 GiB RLIMIT_AS cap)
with ``control_plane`` dense vs sparse: dense ships W·5 B of
certs+flags every round, sparse only each device's top-k
(cert, global id, round) triples at 12 B each. Under uniform delay the
end state must stay digest-identical (the suppressed-runner-up argument
in docs/architecture.md) and at W=10240 the per-round control bytes
must collapse >= 10x — both enforced loudly. A het-delay pair at
W=4096 then measures (reports, never asserts) the sparse-control
approximation gap, exactly like the gated-gossip and bounded-queue
sections above.

The *pod* section runs W=256 on a hierarchical (2, 4) ``(pod, workers)``
mesh and reports the two interconnect tiers separately — intra-pod
all_gather bytes/round (ICI) vs amortized cross-pod candidate-exchange
bytes/round (DCN) — at ``cross_pod_every_k ∈ {1, 8}``. k=1 must match
the flat 8-device engine bit-identically (certs digest, uniform delay;
a mismatch fails the bench); k=8 must cut amortized DCN bytes ≥ 5x,
and its certificate divergence from the flat run is *reported* as a
measured approximation gap, never assumed away.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.boosting import BatchedSparrowWorker, SparrowConfig, SparrowWorker
from repro.boosting.scanner import ScannerConfig
from repro.core.engine import EngineConfig, TMSNEngine
from repro.core.simulator import SimulatorConfig, TMSNSimulator, WorkerSpec
from repro.data.splice import SpliceConfig, make_splice_like, train_test_split

RESULTS = os.path.join(os.path.dirname(__file__), "results")

TARGET_CERT = -0.06


def _data(quick: bool):
    n = 30_000 if quick else 60_000
    # d >= max sweep W: ownership assigns feature j to worker j mod W,
    # so d < W leaves workers >= d with zero features — they could never
    # fire and rounds_to_target at large W would be vacuous.
    d = 128 if quick else 256
    xb, y, _ = make_splice_like(SpliceConfig(n=n, d=d, num_bins=8, seed=11))
    xtr, ytr, _, _ = train_test_split(xb, y)
    return xtr, ytr


def _sparrow_cfg(w: int) -> SparrowConfig:
    return SparrowConfig(
        sample_size=1024,
        capacity=48,
        scanner=ScannerConfig(chunk_size=256, num_bins=8, gamma0=0.25),
        n_workers=w,
    )


def _run_engine(xtr, ytr, w: int, max_rounds: int) -> dict:
    worker = BatchedSparrowWorker(xtr, ytr, _sparrow_cfg(w))
    eng = TMSNEngine(
        worker,
        EngineConfig(
            n_workers=w,
            max_rounds=max_rounds,
            target_certificate=TARGET_CERT,
            seed=0,
            record_history=False,
            rounds_per_dispatch=8,  # explicit: baselines must not move with env overrides
        ),
    )
    res = eng.run()  # first run pays jit compilation
    t0 = time.time()
    res = eng.run()  # second run reuses the compiled round step
    wall = time.time() - t0
    out = {
        "rounds_to_target": res.rounds,
        "hit_target": min(res.final_certificates) <= TARGET_CERT,
        "best_cert": min(res.final_certificates),
        "wall_s": wall,
        "wall_ms_per_round": 1e3 * wall / max(res.rounds, 1),
        "per_segment_us": 1e6 * wall / max(res.rounds * w, 1),
        "messages_sent": res.messages_sent,
        "messages_accepted": res.messages_accepted,
    }
    return out


def _run_dispatch_chunk(xtr, ytr, w: int, rounds: int, rpd: int) -> dict:
    """Fixed-round throughput run (no target, no history: zero host
    syncs inside the loop) at a given rounds_per_dispatch."""
    worker = BatchedSparrowWorker(xtr, ytr, _sparrow_cfg(w))
    eng = TMSNEngine(
        worker,
        EngineConfig(
            n_workers=w,
            max_rounds=rounds,
            seed=0,
            record_history=False,
            rounds_per_dispatch=rpd,
        ),
    )
    eng.run()  # compile
    t0 = time.time()
    res = eng.run()
    wall = time.time() - t0
    return {
        "rounds_per_dispatch": rpd,
        "rounds": res.rounds,
        "wall_ms_per_round": 1e3 * wall / max(res.rounds, 1),
    }


SHARDED_DEVICES = 8


class _RoundOnlyWorker:
    """Trivial-segment worker for isolating the round machinery.

    Sparrow's per-worker segment costs ~2.5 ms of scan compute, so at
    W=1024 the end-to-end wall is worker-compute-bound and the in-flight
    representation is invisible in it. This worker's segment is O(1)
    (decrement a counter, maybe improve the certificate), so a run's
    wall is almost entirely the gossip + in-flight + delivery machinery
    — the thing the dense-buffer/sparse-queue comparison is about.
    Mirrors the shard-map worker contract: per-worker constants live in
    the state pytree.
    """

    def __init__(self, w: int):
        import jax.numpy as jnp

        self._period = jnp.asarray(1 + np.arange(w) % 4, jnp.int32)
        self._dec = jnp.asarray(0.01 + 0.001 * (np.arange(w) % 7), jnp.float32)

    def init_batch(self, n_workers, seed):
        import jax.numpy as jnp

        z = jnp.zeros((n_workers,), jnp.int32)
        return {
            "segs": z,
            "fires": z,
            "cert": jnp.zeros((n_workers,), jnp.float32),
            "owner": jnp.arange(n_workers, dtype=jnp.int32),
            "period": self._period,
            "dec": self._dec,
        }

    def scan_round(self, state, mask):
        import jax.numpy as jnp

        segs = state["segs"] + mask.astype(jnp.int32)
        fired = mask & (segs % state["period"] == 0)
        fires = state["fires"] + fired.astype(jnp.int32)
        own = -state["dec"] * fires
        cert = jnp.where(fired, jnp.minimum(state["cert"], own), state["cert"])
        new = dict(state, segs=segs, fires=fires, cert=cert)
        return new, mask.astype(jnp.float32), fired

    # no resample hooks: the engines detect their absence at build time
    # and statically drop the resample branch (repro.core.worker), so
    # the sweep measures the lean round path

    def certificates(self, state):
        return state["cert"]

    def export_models(self, state):
        return {"owner": state["owner"], "cert": state["cert"]}

    def adopt_batch(self, state, models, certs, take):
        import jax.numpy as jnp

        new = dict(state)
        new["cert"] = jnp.where(take, certs, state["cert"])
        return new, jnp.zeros(state["cert"].shape, jnp.float32)

    def payload_bytes(self):
        return 8


def _sharded_child(
    w: int,
    n_dev: int,
    rounds: int,
    gossip_mode: str,
    pods: int = 1,
    cross_k: int = 1,
    capacity: int = 0,
    delay_profile: str = "uniform",
    mem_gb: int = 0,
    worker_kind: str = "sparrow",
    control_plane: str = "dense",
    fault_spec: str = "",
    churn: int = 0,
) -> dict:
    """Runs inside the subprocess (forced host devices already in env):
    one shard-mapped engine run of ``rounds`` rounds, timed after a
    compile run, JSON result on stdout. ``pods > 1`` runs the
    hierarchical (pod, workers) mesh with the given cross-pod cadence.
    ``capacity > 0`` swaps the dense in-flight buffer for the sparse
    pending queue; ``delay_profile="hetD"`` freezes per-link delays in
    [1, D]; ``mem_gb > 0`` caps the child's address space (RLIMIT_AS) so
    the dense-path memory wall is a hard, reproducible failure instead
    of an allocator-dependent slowdown; ``worker_kind="toy"`` swaps the
    Sparrow worker for :class:`_RoundOnlyWorker` so the wall isolates
    the round machinery; ``control_plane="sparse"`` swaps the dense
    certs/flags control gather for top-k candidate triples;
    ``fault_spec`` injects a FaultPlan (same spec string as
    REPRO_FAULT_PLAN); ``churn = N`` reserves N spare slots and drives a
    churn trace — N spares join and N founding workers leave, spread
    evenly over the middle of the run."""
    import hashlib

    from repro.core.engine import EngineConfig, MembershipPlan, make_engine, quantize_latency
    from repro.launch.mesh import make_worker_mesh

    if mem_gb:
        import resource

        cap_bytes = mem_gb << 30
        resource.setrlimit(resource.RLIMIT_AS, (cap_bytes, cap_bytes))

    delay_rounds: object = 1
    if delay_profile.startswith("het"):
        # latencies in [0.01, 0.01 * depth) at dt=0.01 -> delays in [1, depth]
        depth = int(delay_profile[3:])
        delay_rounds = quantize_latency(0.01, 0.01 * (depth - 1), 0.01, w, seed=0)

    if worker_kind == "toy":
        worker: object = _RoundOnlyWorker(w)
    else:
        # scaled-down per-worker footprint so W=1024 fits a CPU host:
        # d=128 features, 256-example samples (throughput/traffic profile)
        xb, y, _ = make_splice_like(SpliceConfig(n=20_000, d=128, num_bins=8, seed=11))
        xtr, ytr, _, _ = train_test_split(xb, y)
        cfg = SparrowConfig(
            sample_size=256,
            capacity=32,
            scanner=ScannerConfig(chunk_size=128, num_bins=8, gamma0=0.25),
            n_workers=w,
        )
        worker = BatchedSparrowWorker(xtr, ytr, cfg)
    membership = None
    if churn:
        # churn trace: the top `churn` slots are spares that join at
        # rounds spread over [2, rounds - 2]; the first `churn` founding
        # workers leave over the same window (join + leave = fail-stop
        # composition, so the run must complete without deadlock)
        lo, hi = 2, max(3, rounds - 2)
        span = max(1, hi - lo)
        membership = MembershipPlan(
            joins=tuple(
                (lo + (i * span) // churn, w - churn + i) for i in range(churn)
            ),
            leaves=tuple((lo + (i * span) // churn, i) for i in range(churn)),
        )
    eng = make_engine(
        worker,
        EngineConfig(
            n_workers=w,
            max_rounds=rounds,
            seed=0,
            record_history=False,
            mesh=make_worker_mesh(n_dev, pods=pods),
            gossip_mode=gossip_mode,
            rounds_per_dispatch=8,  # explicit: baselines must not move with env
            cross_pod_every_k=cross_k,  # explicit, like rounds_per_dispatch
            cross_pod_top_k=1,
            inflight_capacity=capacity,
            delay_rounds=delay_rounds,
            control_plane=control_plane,
            fault_spec=fault_spec,  # explicit: "" pins chaos OFF despite env
            spare_slots=churn,
            membership=membership,
        ),
    )
    res = eng.run()  # compile
    t0 = time.time()
    res = eng.run()
    wall = time.time() - t0
    certs = np.asarray(res.final_certificates, np.float32)
    return {
        "w": w,
        "devices": n_dev,
        "pods": pods,
        "cross_pod_every_k": cross_k,
        "rounds": res.rounds,
        "gossip_mode": res.gossip_mode,
        "wall_ms_per_round": 1e3 * wall / max(res.rounds, 1),
        "per_segment_us": 1e6 * wall / max(res.rounds * w, 1),
        "gossip_bytes_per_round": res.gossip_bytes_per_round,
        "gossip_bytes_per_round_ici": res.gossip_bytes_per_round_ici,
        "gossip_bytes_per_round_dcn": res.gossip_bytes_per_round_dcn,
        "gossip_mb_total": res.gossip_bytes_per_round * res.rounds / 1e6,
        "messages_sent": res.messages_sent,
        "messages_sent_dcn": res.messages_sent_dcn,
        "messages_accepted": res.messages_accepted,
        "messages_evicted": res.messages_evicted,
        "inflight_capacity": capacity,
        "inflight_occupancy_peak": res.inflight_occupancy_peak,
        "control_plane": res.control_plane,
        "control_bytes_per_round": res.control_bytes_per_round,
        "messages_dropped_injected": res.messages_dropped_injected,
        "messages_corrupt_rejected": res.messages_corrupt_rejected,
        "workers_joined": res.workers_joined,
        "best_cert": min(res.final_certificates),
        # digest of ALL final certs so the parent can check dense/gated
        # end-state identity (uniform delay) without shipping W floats
        "certs_digest": hashlib.sha1(certs.tobytes()).hexdigest(),
    }


def _skip_host_sweeps(bench: str) -> bool:
    """True (after saying why) when this process runs on a TPU. The
    sharded sections run in children pinned to forced CPU host devices;
    under a TPU parent their numbers would be CPU numbers reported in a
    TPU run. The sharded engine on real chips is exercised by
    ``python chip_smoke.py --chips 4``."""
    import jax

    if jax.default_backend() != "tpu":
        return False
    print(
        f"# {bench}: skipping the sharded sections on a TPU: their children run on "
        "forced CPU host devices; run `python chip_smoke.py --chips 4` for the "
        "sharded engine on chips",
        file=sys.stderr,
        flush=True,
    )
    return True


def _write_results(name: str, out: dict) -> None:
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, name), "w") as f:
        json.dump(out, f, indent=1, default=float)


def _run_sharded(
    w: int,
    rounds: int,
    gossip_mode: str = "dense",
    pods: int = 1,
    cross_k: int = 1,
    capacity: int = 0,
    delay_profile: str = "uniform",
    mem_gb: int = 0,
    worker_kind: str = "sparrow",
    control_plane: str = "dense",
    fault_spec: str = "",
    churn: int = 0,
    check: bool = True,
    timeout: int = 3600,
) -> dict:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # the forced device count only applies to the HOST platform — pin
    # the child to cpu so a machine with a real accelerator still runs
    # the 8-way host sweep instead of crashing on a 1-device GPU mesh
    env["JAX_PLATFORMS"] = "cpu"
    # appended AFTER any inherited flags: XLA flag parsing is last-wins,
    # so the child's forced device count must come last to stick
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={SHARDED_DEVICES}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(root, "src"), env.get("PYTHONPATH", "")] if p
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_scaling",
             "--sharded-child", str(w), str(SHARDED_DEVICES), str(rounds), gossip_mode,
             str(pods), str(cross_k), str(capacity), delay_profile, str(mem_gb),
             worker_kind, control_plane, fault_spec, str(churn)],
            env=env,
            cwd=root,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        # an address-space-capped child can wedge instead of dying (one
        # device thread OOMs inside a collective while the rest wait at
        # the rendezvous) — for expected-failure probes that is still
        # just "did not complete"
        if not check:
            return {"completed": False, "w": w, "mem_gb": mem_gb, "error_tail": "timeout"}
        raise
    if proc.returncode != 0:
        if not check:
            # expected-failure probe (the dense memory-wall attempt):
            # report what happened instead of raising
            return {
                "completed": False,
                "w": w,
                "mem_gb": mem_gb,
                "error_tail": (proc.stderr or proc.stdout)[-400:],
            }
        raise RuntimeError(
            f"sharded child W={w} ({gossip_mode}, pods={pods}, k={cross_k}, "
            f"capacity={capacity}, delay={delay_profile}, mem_gb={mem_gb}, "
            f"control={control_plane}, faults={fault_spec!r}, churn={churn}) failed:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    # the child prints exactly one JSON line last (jax may warn above it)
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    res["completed"] = True
    return res


def run(quick: bool = False) -> list[str]:
    lines: list[str] = []
    out: dict = {}
    xtr, ytr = _data(quick)
    sweep = (8, 32, 128) if quick else (8, 32, 128, 256)
    max_rounds = 200 if quick else 400

    for w in sweep:
        res = _run_engine(xtr, ytr, w, max_rounds)
        out[f"w{w}"] = res
        lines.append(f"scaling.w{w}.rounds_to_target,{res['rounds_to_target']},cap_{max_rounds}")
        lines.append(f"scaling.w{w}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},")
        lines.append(f"scaling.w{w}.per_segment_us,{res['per_segment_us']:.0f},")
        lines.append(f"scaling.w{w}.best_cert,{res['best_cert']:.4f},target_{TARGET_CERT}")

    # engine vs event-sim per-segment cost at a size the sim can still run
    w = 8
    worker = SparrowWorker(xtr, ytr, _sparrow_cfg(w))
    ev = 400 if quick else 1600
    sim = TMSNSimulator(
        worker,
        [WorkerSpec() for _ in range(w)],
        SimulatorConfig(n_workers=w, max_events=ev, seed=0),
    )
    sim.run()  # warm the per-segment jit caches
    t0 = time.time()
    res_sim = sim.run()
    sim_wall = time.time() - t0
    sim_us = 1e6 * sim_wall / max(res_sim.events_processed, 1)
    out["sim_w8"] = {"events": res_sim.events_processed, "per_event_us": sim_us}
    speedup = sim_us / max(out["w8"]["per_segment_us"], 1e-9)
    out["engine_speedup_vs_sim_w8"] = speedup
    lines.append(f"scaling.sim_w8.per_event_us,{sim_us:.0f},event_driven_oracle")
    lines.append(f"scaling.w8.engine_speedup_vs_sim,{speedup:.1f},per_segment_ratio")

    # --- dispatch-chunk sweep: wall/round vs rounds_per_dispatch ----------
    # >= 2 full chunks at the largest rpd, so every sweep point actually
    # measures its labeled chunk size (run() clamps a chunk to the
    # rounds remaining)
    w = 128
    disp_rounds = 64
    for rpd in (1, 8, 32):
        res = _run_dispatch_chunk(xtr, ytr, w, disp_rounds, rpd)
        out[f"dispatch_w{w}_rpd{rpd}"] = res
        lines.append(
            f"scaling.dispatch_w{w}_rpd{rpd}.wall_ms_per_round,"
            f"{res['wall_ms_per_round']:.1f},{disp_rounds}_rounds"
        )
    speedup = (
        out[f"dispatch_w{w}_rpd1"]["wall_ms_per_round"]
        / max(out[f"dispatch_w{w}_rpd8"]["wall_ms_per_round"], 1e-9)
    )
    out["dispatch_w128_speedup_rpd8_vs_rpd1"] = speedup
    lines.append(f"scaling.dispatch_w{w}.speedup_rpd8_vs_rpd1,{speedup:.2f},wall_ratio")

    if _skip_host_sweeps("scaling"):
        _write_results("scaling.json", out)
        return lines

    # --- sharded engine sweep across forced host devices ------------------
    from repro.launch.mesh import ici_round_seconds

    rounds = 6 if quick else 20
    for w in (64, 256, 1024):
        res = _run_sharded(w, rounds)
        out[f"sharded_w{w}"] = res
        pre = f"scaling.sharded_w{w}"
        lines.append(f"{pre}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},{SHARDED_DEVICES}_devices")
        lines.append(f"{pre}.per_segment_us,{res['per_segment_us']:.0f},")
        lines.append(f"{pre}.gossip_bytes_per_round,{res['gossip_bytes_per_round']},all_gather_footprint")
        lines.append(f"{pre}.messages_sent,{res['messages_sent']},{res['rounds']}_rounds")

    # gated gossip: payloads only for top-k improved candidates; end
    # state must stay identical to dense under the (uniform) delay here
    for w in (256, 1024):
        res = _run_sharded(w, rounds, gossip_mode="gated")
        out[f"sharded_w{w}_gated"] = res
        pre = f"scaling.sharded_w{w}_gated"
        dense = out[f"sharded_w{w}"]
        reduction = dense["gossip_bytes_per_round"] / max(res["gossip_bytes_per_round"], 1)
        identical = int(res["certs_digest"] == dense["certs_digest"])
        if not identical:
            # uniform delay: gated MUST reproduce dense exactly — a
            # mismatch is an equivalence regression, not noise, and has
            # to fail the bench (and with it the full CI tier) loudly
            raise RuntimeError(
                f"gated gossip diverged from dense at W={w} under uniform delay: "
                f"certs digest {res['certs_digest']} != {dense['certs_digest']}"
            )
        lines.append(f"{pre}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},{SHARDED_DEVICES}_devices")
        lines.append(
            f"{pre}.gossip_bytes_per_round,{res['gossip_bytes_per_round']},"
            f"vs_{dense['gossip_bytes_per_round']}_dense"
        )
        lines.append(f"{pre}.gossip_reduction_x,{reduction:.1f},dense_over_gated")
        lines.append(f"{pre}.certs_identical_to_dense,{identical},uniform_delay")
        lines.append(
            f"{pre}.ici_us_per_round,{1e6 * ici_round_seconds(res['gossip_bytes_per_round']):.1f},"
            f"vs_{1e6 * ici_round_seconds(dense['gossip_bytes_per_round']):.1f}_dense"
        )

    # --- hierarchical (pod, workers) mesh: ICI vs DCN traffic tiers -------
    # W=256 on a (2, 4) pod mesh. cross_pod_every_k=1 must reproduce the
    # flat 8-device dense run bit-identically (uniform delay); k=8 is the
    # approximation regime — per-k certificate divergence is REPORTED
    # (measured, never assumed), while the amortized DCN footprint must
    # collapse ~k-fold.
    from repro.launch.mesh import dcn_round_seconds

    w = 256
    pod_sweep = {}
    for k in (1, 8):
        res = _run_sharded(w, rounds, gossip_mode="dense", pods=2, cross_k=k)
        pod_sweep[k] = res
        out[f"pod2_w{w}_k{k}"] = res
        pre = f"scaling.pod2_w{w}_k{k}"
        lines.append(f"{pre}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},2x4_pod_mesh")
        lines.append(f"{pre}.ici_bytes_per_round,{res['gossip_bytes_per_round_ici']},intra_pod_all_gather")
        lines.append(f"{pre}.dcn_bytes_per_round,{res['gossip_bytes_per_round_dcn']},cross_pod_amortized")
        lines.append(f"{pre}.messages_sent_dcn,{res['messages_sent_dcn']},{res['rounds']}_rounds")
        lines.append(
            f"{pre}.dcn_us_per_round,{1e6 * dcn_round_seconds(res['gossip_bytes_per_round_dcn']):.1f},"
            f"derived_wire_time"
        )
    flat_dense = out[f"sharded_w{w}"]
    if pod_sweep[1]["certs_digest"] != flat_dense["certs_digest"]:
        # uniform delay + k=1: the pod mesh MUST reproduce the flat
        # engine exactly — a mismatch is an equivalence regression and
        # has to fail the bench (and with it the full CI tier) loudly
        raise RuntimeError(
            f"pod mesh diverged from the flat engine at W={w}, cross_pod_every_k=1: "
            f"certs digest {pod_sweep[1]['certs_digest']} != {flat_dense['certs_digest']}"
        )
    lines.append(f"scaling.pod2_w{w}_k1.certs_identical_to_flat,1,uniform_delay")
    dcn_drop = pod_sweep[1]["gossip_bytes_per_round_dcn"] / max(
        pod_sweep[8]["gossip_bytes_per_round_dcn"], 1
    )
    if dcn_drop < 5.0:
        raise RuntimeError(
            f"cross_pod_every_k=8 only cut amortized DCN bytes/round {dcn_drop:.1f}x "
            f"(expected >= 5x) at W={w}"
        )
    out[f"pod2_w{w}_dcn_reduction_k8_vs_k1"] = dcn_drop
    lines.append(f"scaling.pod2_w{w}_k8.dcn_reduction_x_vs_k1,{dcn_drop:.1f},amortized")
    # measured approximation gap, reported not asserted
    gap = abs(pod_sweep[8]["best_cert"] - flat_dense["best_cert"])
    out[f"pod2_w{w}_k8_best_cert_gap_vs_flat"] = gap
    lines.append(f"scaling.pod2_w{w}_k8.best_cert_gap_vs_flat,{gap:.5f},measured_divergence")

    # --- sparse in-flight state: pending queues + fused round kernel ------
    # (i) uniform delay, W=1024, C=64: worst-first eviction preserves the
    # per-round delivery argmin when every pending entry shares the same
    # due round, so the end state must be digest-IDENTICAL to the dense
    # run above — a mismatch is an equivalence regression and fails the
    # bench loudly. Wall/round is reported against both the committed
    # baseline and the same-run dense number (same machine, same noise).
    w, cap = 1024, 64
    res = _run_sharded(w, rounds, capacity=cap)
    out[f"sparse_w{w}"] = res
    dense = out[f"sharded_w{w}"]
    if res["certs_digest"] != dense["certs_digest"]:
        raise RuntimeError(
            f"sparse in-flight state diverged from dense at W={w} under uniform "
            f"delay: certs digest {res['certs_digest']} != {dense['certs_digest']}"
        )
    pre = f"scaling.sparse_w{w}"
    lines.append(f"{pre}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},capacity_{cap}")
    lines.append(f"{pre}.certs_identical_to_dense,1,uniform_delay")
    lines.append(f"{pre}.inflight_occupancy_peak,{res['inflight_occupancy_peak']},capacity_{cap}")
    lines.append(f"{pre}.messages_evicted,{res['messages_evicted']},accounted_drops")
    same_run = dense["wall_ms_per_round"] / max(res["wall_ms_per_round"], 1e-9)
    out[f"sparse_w{w}_speedup_vs_same_run_dense"] = same_run
    lines.append(f"{pre}.speedup_vs_same_run_dense,{same_run:.2f},wall_ratio")
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baseline.json")
    if os.path.exists(base_path):
        with open(base_path) as f:
            base_ms = (
                json.load(f)
                .get("metrics", {})
                .get(f"scaling.sharded_w{w}.wall_ms_per_round", {})
                .get("value")
            )
        if base_ms:
            sp = base_ms / max(res["wall_ms_per_round"], 1e-9)
            out[f"sparse_w{w}_speedup_vs_baseline"] = sp
            lines.append(
                f"{pre}.speedup_vs_baseline,{sp:.2f},vs_committed_dense_{base_ms:g}ms"
            )

    # (ii) round-machinery isolation, W=1024, delays in [1, 256], 24
    # rounds: Sparrow's ~2.5 ms/worker segment makes the end-to-end wall
    # above worker-compute-bound (per_segment_us is flat across W), so
    # the in-flight representation cannot move it — the sparse win lives
    # where the round machinery IS the cost. A trivial-segment worker
    # (_RoundOnlyWorker) at delay depth 256 makes the dense per-shard
    # (W/n_dev, W, 256) f32 buffer (128 MiB/shard, shifted every round)
    # the dominant per-round cost; the sparse queue carries (W, C) x 16 B
    # regardless of depth. This ratio is the headline wall-ms/round
    # improvement claim and must stay >= 2x — same profile, same run,
    # same machine on both sides.
    ro_rounds, ro_depth = 24, 256
    ro_dense = _run_sharded(
        w, ro_rounds, gossip_mode="gated", delay_profile=f"het{ro_depth}", worker_kind="toy"
    )
    ro_sparse = _run_sharded(
        w, ro_rounds, gossip_mode="gated", capacity=cap,
        delay_profile=f"het{ro_depth}", worker_kind="toy",
    )
    out[f"roundstate_w{w}_d{ro_depth}_dense"] = ro_dense
    out[f"roundstate_w{w}_d{ro_depth}"] = ro_sparse
    ro_speedup = ro_dense["wall_ms_per_round"] / max(ro_sparse["wall_ms_per_round"], 1e-9)
    out[f"roundstate_w{w}_d{ro_depth}_speedup"] = ro_speedup
    pre = f"scaling.roundstate_w{w}_d{ro_depth}"
    lines.append(
        f"{pre}.dense_wall_ms_per_round,{ro_dense['wall_ms_per_round']:.1f},toy_worker"
    )
    lines.append(
        f"{pre}.sparse_wall_ms_per_round,{ro_sparse['wall_ms_per_round']:.1f},capacity_{cap}"
    )
    lines.append(f"{pre}.speedup_x,{ro_speedup:.2f},dense_over_sparse_wall")
    lines.append(
        f"{pre}.messages_evicted,{ro_sparse['messages_evicted']},{ro_sparse['rounds']}_rounds"
    )
    lines.append(
        f"{pre}.inflight_occupancy_peak,{ro_sparse['inflight_occupancy_peak']},capacity_{cap}"
    )
    lines.append(
        f"{pre}.certs_identical_to_dense,"
        f"{int(ro_sparse['certs_digest'] == ro_dense['certs_digest'])},het_delay_approx"
    )
    if ro_speedup < 2.0:
        raise RuntimeError(
            f"sparse in-flight state only {ro_speedup:.2f}x faster than the dense "
            f"buffer on the round-machinery benchmark (W={w}, depth={ro_depth}; "
            "expected >= 2x) — the bounded-queue wall-time claim no longer holds"
        )

    # (iii) heterogeneous delays in [1, 32] at W=1024: with mixed due
    # rounds a bounded queue IS an approximation (an evicted entry could
    # have won a later round's argmin), so the dense-vs-sparse gap is
    # MEASURED and reported — never asserted away. The occupancy peak
    # shows the capacity a bit-exact run would have needed.
    het_d = _run_sharded(w, rounds, delay_profile="het32")
    het_s = _run_sharded(w, rounds, capacity=cap, delay_profile="het32")
    out[f"sparse_w{w}_het32_dense"] = het_d
    out[f"sparse_w{w}_het32"] = het_s
    pre = f"scaling.sparse_w{w}_het32"
    lines.append(f"{pre}.wall_ms_per_round,{het_s['wall_ms_per_round']:.1f},capacity_{cap}")
    lines.append(
        f"{pre}.dense_wall_ms_per_round,{het_d['wall_ms_per_round']:.1f},same_run_dense"
    )
    lines.append(f"{pre}.messages_evicted,{het_s['messages_evicted']},{het_s['rounds']}_rounds")
    lines.append(
        f"{pre}.inflight_occupancy_peak,{het_s['inflight_occupancy_peak']},"
        f"exactness_needs_this_capacity"
    )
    gap = abs(het_s["best_cert"] - het_d["best_cert"])
    out[f"sparse_w{w}_het32_best_cert_gap"] = gap
    lines.append(f"{pre}.best_cert_gap_vs_dense,{gap:.5f},measured_divergence")
    lines.append(
        f"{pre}.certs_identical_to_dense,"
        f"{int(het_s['certs_digest'] == het_d['certs_digest'])},het_delay_approx"
    )

    # (iv) W=4096, delays in [1, 64], hard 9 GiB address-space cap: the
    # dense in-flight buffer is a single 4 GiB (4096, 4096, 64) f32
    # allocation plus its per-round shift copy (~8.6 GiB before any
    # worker state or runtime), so the dense attempt MUST die at
    # allocation while the sparse path (queues are W x C x 16 B, ~6.3
    # GiB peak address space all-in) completes the sweep under the
    # same cap.
    w4, mem_gb = 4096, 9
    dense4 = _run_sharded(
        w4, rounds, delay_profile="het64", mem_gb=mem_gb, check=False, timeout=1800
    )
    if dense4["completed"]:
        raise RuntimeError(
            f"dense in-flight buffer unexpectedly fit W={w4} under a {mem_gb} GiB "
            "address-space cap — the sparse memory-wall claim no longer holds"
        )
    sparse4 = _run_sharded(w4, rounds, capacity=cap, delay_profile="het64", mem_gb=mem_gb)
    out[f"dense_w{w4}_capped"] = dense4
    out[f"sparse_w{w4}"] = sparse4
    pre = f"scaling.sparse_w{w4}"
    lines.append(f"{pre}.completed,1,under_{mem_gb}gib_cap")
    lines.append(f"scaling.dense_w{w4}.completed,0,under_{mem_gb}gib_cap")
    lines.append(f"{pre}.wall_ms_per_round,{sparse4['wall_ms_per_round']:.1f},capacity_{cap}")
    lines.append(f"{pre}.per_segment_us,{sparse4['per_segment_us']:.0f},")
    lines.append(f"{pre}.messages_evicted,{sparse4['messages_evicted']},{sparse4['rounds']}_rounds")

    # --- control plane: dense certs/flags vs top-k candidate triples ------
    # W ∈ {4096, 10240} on the toy worker (round machinery is the cost),
    # gated gossip, sparse in-flight capacity 64, uniform delay, under
    # the same hard 9 GiB address-space cap as the memory-wall run — the
    # large-W regime the sparse control plane exists for. Dense control
    # gathers W_tier · 5 bytes of certs+flags every round; sparse
    # control ships only n_dev · k · 12 bytes of (cert, id, round)
    # triples. Under uniform delay the end state MUST be
    # digest-identical (suppressed runner-ups can never win a delivery
    # argmin — docs/architecture.md), and at W=10240 the control bytes
    # must collapse >= 10x — both failures are loud, not reported.
    for wc in (4096, 10240):
        pair = {}
        for plane in ("dense", "sparse"):
            res = _run_sharded(
                wc, rounds, gossip_mode="gated", capacity=cap, worker_kind="toy",
                mem_gb=9, control_plane=plane,
            )
            pair[plane] = res
            out[f"ctrl_w{wc}_{plane}"] = res
            pre = f"scaling.ctrl_w{wc}_{plane}"
            lines.append(
                f"{pre}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},9gib_cap"
            )
            lines.append(
                f"{pre}.control_bytes_per_round,{res['control_bytes_per_round']},"
                f"{plane}_control"
            )
            lines.append(
                f"{pre}.gossip_bytes_per_round,{res['gossip_bytes_per_round']},incl_control"
            )
            lines.append(
                f"{pre}.ici_us_per_round,"
                f"{1e6 * ici_round_seconds(res['gossip_bytes_per_round']):.1f},"
                f"derived_wire_time"
            )
        if pair["sparse"]["certs_digest"] != pair["dense"]["certs_digest"]:
            # uniform delay: sparse control MUST reproduce dense control
            # exactly — a mismatch is an equivalence regression, not
            # noise, and has to fail the bench loudly
            raise RuntimeError(
                f"sparse control plane diverged from dense at W={wc} under uniform "
                f"delay: certs digest {pair['sparse']['certs_digest']} != "
                f"{pair['dense']['certs_digest']}"
            )
        lines.append(f"scaling.ctrl_w{wc}_sparse.certs_identical_to_dense,1,uniform_delay")
        ctrl_drop = pair["dense"]["control_bytes_per_round"] / max(
            pair["sparse"]["control_bytes_per_round"], 1
        )
        out[f"ctrl_w{wc}_reduction_sparse_vs_dense"] = ctrl_drop
        lines.append(
            f"scaling.ctrl_w{wc}_sparse.control_reduction_x,{ctrl_drop:.1f},"
            f"dense_over_sparse"
        )
        if wc == 10240 and ctrl_drop < 10.0:
            raise RuntimeError(
                f"sparse control plane only cut control bytes/round {ctrl_drop:.1f}x "
                f"at W={wc} (expected >= 10x) — the sparse-control traffic claim "
                "no longer holds"
            )

    # heterogeneous delays at W=4096: with mixed due rounds a suppressed
    # runner-up CAN win a later delivery argmin, so sparse control is an
    # approximation — the dense-vs-sparse certificate gap is MEASURED
    # and reported, never asserted away.
    wc = 4096
    chet_d = _run_sharded(
        wc, rounds, gossip_mode="gated", capacity=cap, worker_kind="toy",
        delay_profile="het32", control_plane="dense",
    )
    chet_s = _run_sharded(
        wc, rounds, gossip_mode="gated", capacity=cap, worker_kind="toy",
        delay_profile="het32", control_plane="sparse",
    )
    out[f"ctrl_w{wc}_het32_dense"] = chet_d
    out[f"ctrl_w{wc}_het32_sparse"] = chet_s
    pre = f"scaling.ctrl_w{wc}_het32"
    gap = abs(chet_s["best_cert"] - chet_d["best_cert"])
    out[f"ctrl_w{wc}_het32_best_cert_gap"] = gap
    lines.append(f"{pre}.best_cert_gap_vs_dense,{gap:.5f},measured_divergence")
    lines.append(
        f"{pre}.certs_identical_to_dense,"
        f"{int(chet_s['certs_digest'] == chet_d['certs_digest'])},het_delay_approx"
    )

    # roofline accounting of the fused delivery kernel at the sweep sizes
    from repro.launch.hlo_analysis import round_step_roofline

    for rw in (1024, w4):
        rf = round_step_roofline(rw, cap)
        out[f"round_step_roofline_w{rw}_c{cap}"] = rf
        pre = f"scaling.round_step_w{rw}_c{cap}"
        lines.append(
            f"{pre}.arith_intensity,{rf['arith_intensity_flops_per_byte']:.3f},"
            f"ridge_{rf['ridge_point_flops_per_byte']:.0f}_{rf['bound']}_bound"
        )
        lines.append(f"{pre}.projected_us,{rf['projected_us']:.2f},tpu_v5e_hbm_floor")
        lines.append(
            f"{pre}.fusion_overhead_x,{rf['fusion_overhead_x']:.2f},"
            f"ref_hlo_bytes_over_operand_floor"
        )

    _write_results("scaling.json", out)
    return lines


def run_chaos(quick: bool = False) -> list[str]:
    """Chaos section: the MEASURED side of the fault/membership suite.

    The exact claims (join@k=1 identity, cross-substrate fault
    determinism, duplication transparency, corruption soundness) are
    pinned bit-for-bit in tests/test_chaos.py; what remains is measured
    here and reported, never assumed:

      * a churn trace at W=256 — 64 spares join while 64 founding
        workers leave (a quarter of the cluster churning in each
        direction) — must COMPLETE without deadlock, count exactly 64
        joins, and its best-certificate gap vs the clean run is the
        resilience figure;
      * the CI chaos leg's FaultPlan (drop=3,corrupt=3,seed=9) at
        W=256: injected-drop / rejected-corruption accounting plus the
        cert gap the low-rate faults actually cost;
      * a DCN pod partition on the (2, 4) pod mesh: cross-pod traffic
        severed for the middle third of the run — the two pods keep
        gossiping internally, re-merge when the window closes, and the
        cert gap vs the unpartitioned run measures what the partition
        cost.

    All runs use the trivial-segment worker (the chaos machinery, not
    worker compute, is under test), gated gossip, and the sparse
    pending-queue in-flight state — the large-W configuration the
    elastic layer exists for."""
    lines: list[str] = []
    out: dict = {}
    if _skip_host_sweeps("chaos"):
        return lines
    w, cap = 256, 64
    rounds = 24 if quick else 48
    kw = dict(gossip_mode="gated", capacity=cap, worker_kind="toy")

    clean = _run_sharded(w, rounds, **kw)
    out["clean"] = clean
    lines.append(f"chaos.clean_w{w}.wall_ms_per_round,{clean['wall_ms_per_round']:.1f},reference")
    lines.append(f"chaos.clean_w{w}.best_cert,{clean['best_cert']:.5f},reference")

    # --- churn trace: 64 joins + 64 leaves = a quarter churning each way
    churn = w // 4
    res = _run_sharded(w, rounds, churn=churn, **kw)
    out["churn"] = res
    if res["workers_joined"] != churn:
        # join accounting is exact — a miscount is a regression, not noise
        raise RuntimeError(
            f"churn trace joined {res['workers_joined']} workers, expected {churn}"
        )
    pre = f"chaos.churn_w{w}"
    gap = abs(res["best_cert"] - clean["best_cert"])
    out["churn_best_cert_gap"] = gap
    lines.append(f"{pre}.completed,1,{churn}_join_{churn}_leave_no_deadlock")
    lines.append(f"{pre}.workers_joined,{res['workers_joined']},exact_accounting")
    lines.append(f"{pre}.wall_ms_per_round,{res['wall_ms_per_round']:.1f},capacity_{cap}")
    lines.append(f"{pre}.best_cert_gap_vs_clean,{gap:.5f},measured_divergence")

    # --- the CI chaos leg's fault plan, measured at bench scale ----------
    spec = "drop=3,corrupt=3,seed=9"
    res = _run_sharded(w, rounds, fault_spec=spec, **kw)
    out["faults"] = res
    if res["messages_dropped_injected"] <= 0 or res["messages_corrupt_rejected"] <= 0:
        raise RuntimeError(
            f"fault plan {spec!r} injected nothing "
            f"(dropped={res['messages_dropped_injected']}, "
            f"rejected={res['messages_corrupt_rejected']})"
        )
    pre = f"chaos.faults_w{w}"
    gap = abs(res["best_cert"] - clean["best_cert"])
    out["faults_best_cert_gap"] = gap
    tag = spec.replace("=", "").replace(",", "_")  # CSV derived col: no commas
    lines.append(f"{pre}.messages_dropped_injected,{res['messages_dropped_injected']},{tag}")
    lines.append(f"{pre}.messages_corrupt_rejected,{res['messages_corrupt_rejected']},eps_gate_soundness")
    lines.append(f"{pre}.best_cert_gap_vs_clean,{gap:.5f},measured_divergence")

    # --- DCN pod partition: cross-pod tier severed mid-run ----------------
    pod_kw = dict(pods=2, cross_k=1, **kw)
    part_lo, part_hi = rounds // 3, 2 * rounds // 3
    pod_clean = _run_sharded(w, rounds, **pod_kw)
    pod_part = _run_sharded(
        w, rounds, fault_spec=f"part={part_lo}:{part_hi},seed=9", **pod_kw
    )
    out["pod_clean"] = pod_clean
    out["pod_partition"] = pod_part
    if pod_part["messages_dropped_injected"] <= 0:
        raise RuntimeError(
            f"pod partition window [{part_lo}, {part_hi}) dropped no cross-pod "
            "traffic — the partition fault is not reaching the pod tier"
        )
    pre = f"chaos.partition_pod2_w{w}"
    gap = abs(pod_part["best_cert"] - pod_clean["best_cert"])
    out["partition_best_cert_gap"] = gap
    lines.append(f"{pre}.completed,1,window_{part_lo}_{part_hi}_no_deadlock")
    lines.append(f"{pre}.messages_dropped_injected,{pod_part['messages_dropped_injected']},cross_pod_only")
    lines.append(f"{pre}.best_cert_gap_vs_clean,{gap:.5f},measured_divergence")
    lines.append(f"{pre}.wall_ms_per_round,{pod_part['wall_ms_per_round']:.1f},2x4_pod_mesh")

    _write_results("chaos.json", out)
    return lines


def _main() -> None:
    if len(sys.argv) >= 2 and sys.argv[1] == "--sharded-child":
        w, n_dev, rounds = (int(a) for a in sys.argv[2:5])
        mode = sys.argv[5] if len(sys.argv) > 5 else "dense"
        pods = int(sys.argv[6]) if len(sys.argv) > 6 else 1
        cross_k = int(sys.argv[7]) if len(sys.argv) > 7 else 1
        capacity = int(sys.argv[8]) if len(sys.argv) > 8 else 0
        delay_profile = sys.argv[9] if len(sys.argv) > 9 else "uniform"
        mem_gb = int(sys.argv[10]) if len(sys.argv) > 10 else 0
        worker_kind = sys.argv[11] if len(sys.argv) > 11 else "sparrow"
        control_plane = sys.argv[12] if len(sys.argv) > 12 else "dense"
        fault_spec = sys.argv[13] if len(sys.argv) > 13 else ""
        churn = int(sys.argv[14]) if len(sys.argv) > 14 else 0
        print(
            json.dumps(
                _sharded_child(
                    w, n_dev, rounds, mode, pods, cross_k, capacity, delay_profile, mem_gb,
                    worker_kind, control_plane, fault_spec, churn,
                )
            ),
            flush=True,
        )
        return
    for line in run(quick=True):
        print(line)


if __name__ == "__main__":
    _main()
