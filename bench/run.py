#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json`` per run.

    python bench/run.py --workload splice-w16.rounds --seed 7 --seconds 10 --trace 0

A cell names a configuration (``bench/configs/<name>.json``) and a
traffic mix (``bench/traffic/<name>.json``); the harness finds both by
name, and every metric by its name too: an end-to-end metric is read by
``bench/end_to_end/<name>.py``, a per-layer metric by
``bench/layer_metrics/<name>.py``. Each reader has ``read(ctx)`` and
returns a number, or None where it finds nothing to read.

A run generates the configuration's data set on the device, draws the
engine's seed from ``--seed`` (the traffic's ``engine_seeds`` of them,
one by default), builds the worker and an engine for each through the
program's entry point (``make_engine(BatchedSparrowWorker(...),
EngineConfig(...))``), warms each up with one whole training (the
compile, counted as set-up), then runs trainings back to back for
``--seconds``, the engines in turn. With ``--trace 1`` that window is
traced and the per-layer metrics are reported instead of the end-to-end
ones. Once the window has closed and the device's peak memory has been
read, the plain reference (``bench/reference.py``) follows the window's
last training and ``bench/compare.py`` decides ``correct``.

The last line of standard output is the JSON result; the last lines of
standard error are the numbers compared, each beside its limit. Without
a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import hashlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The workload entry, its configuration, traffic, metric entries
    and correctness limits, all found by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    applies = lambda m: workload in m.get("workloads", [workload])
    return {
        "cell": cell,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{cell['config']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def engine_seed(seed: int, k: int = 0) -> int:
    """The ``k``-th engine seed (``< 2**30``) of a run from any whole
    number. The data set is the configuration's own (``data.seed``), as a
    deployment trains on one data set: ``--seed`` draws the workers'
    samples, so every seed runs the same passes over the same data."""
    key = str(int(seed)) if k == 0 else f"{int(seed)}/{k}"
    h = hashlib.sha256(key.encode()).digest()
    return int.from_bytes(h[:4], "little") >> 2


def load_reader(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------


def build_engine(cfg: dict, traffic: dict, xtr, ytr, seed: int, chips: int):
    """The program's own entry point, with every setting the cell
    depends on given explicitly (``EngineConfig`` otherwise reads
    ``REPRO_*`` environment variables)."""
    from repro.boosting import BatchedSparrowWorker, SparrowConfig
    from repro.boosting.scanner import ScannerConfig
    from repro.core.engine import EngineConfig, make_engine

    s, e = cfg["sparrow"], cfg["engine"]
    sc = dict(cfg["scanner"], num_bins=cfg["data"]["num_bins"])
    worker = BatchedSparrowWorker(
        xtr,
        ytr,
        SparrowConfig(
            sample_size=s["sample_size"],
            capacity=s["capacity"],
            scanner=ScannerConfig(**sc),
            ess_threshold=s["ess_threshold"],
            keep_gamma_on_fire=s["keep_gamma_on_fire"],
            n_workers=e["n_workers"],
            ownership_redundancy=s["ownership_redundancy"],
            mem_read_cost=s["mem_read_cost"],
            disk_read_cost=s["disk_read_cost"],
            parallel_sampler=s["parallel_sampler"],
        ),
    )
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_worker_mesh

        mesh = make_worker_mesh(chips)
    to_target = traffic["kind"] == "to_target"
    config = EngineConfig(
        n_workers=e["n_workers"],
        eps=e["eps"],
        max_rounds=traffic["max_rounds"] if to_target else traffic["rounds"],
        delay_rounds=e["delay_rounds"],
        target_certificate=traffic["target_certificate"] if to_target else None,
        seed=seed,
        record_history=traffic["record_history"],
        rounds_per_dispatch=e["rounds_per_dispatch"],
        gossip_mode=e["gossip_mode"],
        gossip_top_k=e["gossip_top_k"],
        cross_pod_every_k=e["cross_pod_every_k"],
        cross_pod_top_k=e["cross_pod_top_k"],
        inflight_capacity=e["inflight_capacity"],
        round_step_impl=e["round_step_impl"],
        control_plane=e["control_plane"],
        spare_slots=e["spare_slots"],
        fault_spec=e["fault_spec"],
        publish_every_k=e["publish_every_k"],
        publish_eps=e["publish_eps"],
        mesh=mesh,
    )
    return make_engine(worker, config)


def one_training(engine):
    """One whole training through the engine's public ``run()``; returns
    its result and its host-clock seconds."""
    import jax

    t0 = time.perf_counter()
    res = engine.run()
    jax.block_until_ready(res.final_models)
    return res, time.perf_counter() - t0


def summary(res, traffic: dict) -> dict:
    import numpy as np

    certs = np.asarray(res.final_certificates, np.float64)
    best = float(certs.min())
    reached = traffic["kind"] != "to_target" or best <= float(np.float32(traffic["target_certificate"]))
    return {
        "rounds": int(res.rounds),
        "certs": certs.tolist(),
        "accepted": int(res.messages_accepted),
        "failed": (not math.isfinite(best)) or not reached,
    }


def observed(res) -> dict:
    """What the training produced, as host arrays, for the comparison."""
    import numpy as np

    models = {
        k: np.stack([np.asarray(getattr(m, k)) for m in res.final_models])
        for k in ("feat", "thr", "sign", "alpha", "count")
    }
    return {
        "rounds": int(res.rounds),
        "history": list(res.history),
        "final_certificates": np.asarray(res.final_certificates, np.float64),
        "models": models,
        "accepted": int(res.messages_accepted),
        "evicted": int(res.messages_evicted),
    }


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    spec: dict | None = None,
    require_chip: bool = True,
) -> dict | None:
    """One run of one cell; returns the result line's object, or None
    where the machine lacks the chips the cell asks for. ``spec``
    replaces what :func:`load_cell` finds (a test's small cell)."""
    import jax

    spec = spec or load_cell(workload)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = int(cell["chips"])
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        log(f"bench: {workload} needs {chips} TPU chip(s); JAX finds {len(devices)} "
            f"{devices[0].platform} device(s)")
        return None
    if require_chip:
        from repro.launch.runtime import enable_compile_cache

        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"bench: compile cache {cache}")

    import reference
    from compare import compare
    from splice_data import make_data

    # the traffic's ``engine_seeds`` engines, each warmed by one whole
    # training; the window runs their trainings in turn
    eseeds = [engine_seed(seed, k) for k in range(int(traffic.get("engine_seeds", 1)))]
    xtr, ytr, _, _ = jax.block_until_ready(make_data(cfg))
    engines, firsts, warm_s = [], [], []
    for es in eseeds:
        engines.append(build_engine(cfg, traffic, xtr, ytr, es, chips))
        warm, dt = one_training(engines[-1])
        firsts.append(summary(warm, traffic))
        warm_s.append(dt)
        del warm
    setup_s = time.perf_counter() - T_START
    log(f"bench: set-up {setup_s:.3f} s (warm-up trainings {', '.join(f'{t:.3f}' for t in warm_s)} s, "
        f"{firsts[0]['rounds']} rounds)")

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans and device events only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    trainings = []
    last = None
    t_window = time.perf_counter()
    while True:
        k = len(trainings) % len(engines)
        with jax.profiler.TraceAnnotation("training"):
            last, dt = one_training(engines[k])
        with jax.profiler.TraceAnnotation("between trainings"):
            trainings.append(dict(summary(last, traffic), seconds=dt, engine=k))
        if time.perf_counter() - t_window >= seconds:
            break
    window_s = time.perf_counter() - t_window
    if trace:
        jax.profiler.stop_trace()
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    log(f"bench: {len(trainings)} trainings in {window_s:.3f} s; rounds {trainings[-1]['rounds']}; "
        f"best certificate {min(trainings[-1]['certs']):.6f}")

    prog = observed(last)
    del last, engines
    gc.collect()

    # --- correct: the plain reference follows the same training ---
    to_target = traffic["kind"] == "to_target"
    params = reference.params_from_config(cfg)
    t_ref = time.perf_counter()
    ref = reference.train(
        xtr, ytr, eseeds[trainings[-1]["engine"]], params,
        max_rounds=traffic["max_rounds"] if to_target else traffic["rounds"],
        target=traffic["target_certificate"] if to_target else None,
    )
    numbers = compare(prog, ref, history=traffic["record_history"])
    decided = lambda t: (t["rounds"], t["certs"], t["accepted"])
    repeats = [t for t in trainings if decided(t) != decided(firsts[t["engine"]])]
    numbers["decisions_differ"] += len(repeats)
    log(f"bench: reference {time.perf_counter() - t_ref:.3f} s, {ref['rounds']} rounds, "
        f"exact until round {ref['exact_until']}")
    limits = spec["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())

    ctx = {
        "cell": cell, "config": cfg, "traffic": traffic, "chips": chips,
        "setup_s": setup_s, "window_s": window_s, "trainings": trainings,
        "device_kind": devices[0].device_kind, "trace": None,
    }
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    result: dict = {"correct": correct, "attempted": len(trainings),
                    "failed": sum(t["failed"] for t in trainings)}
    metrics = {}
    if trace:
        from trace_reduce import find_xplane, reduce_file

        tr = reduce_file(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = tr
        busiest = tr.busiest()
        device["busy_s"] = sum(d.busy_s for d in tr.devices) / len(tr.devices)
        device["window_s"] = tr.window_s
        for m in spec["per_layer"]:
            v = load_reader("layer_metrics", m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown()
        log(f"bench: traced window {tr.window_s:.3f} s, busiest device {busiest.name} busy {busiest.busy_s:.3f} s")
    else:
        for m in spec["end_to_end"]:
            v = load_reader("end_to_end", m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
