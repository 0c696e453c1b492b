#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json`` per run.

    python bench/run.py --workload splice-w16.rounds --seed 7 --seconds 10 --trace 0

A cell names a configuration (``bench/configs/<name>.json``) and a
traffic mix (``bench/traffic/<name>.json``); the harness finds both by
name, and every metric by its name too: an end-to-end metric is read by
``bench/end_to_end/<name>.py``, a per-layer metric by
``bench/layer_metrics/<name>.py``. Each reader has ``read(ctx)`` and
returns a number, or None where it finds nothing to read.

The configuration's ``family`` key names the worker family, found the
same way: ``bench/families/<family>.py``. A configuration without it is
refused. The harness knows nothing of any family, and calls these six
functions of its module:

``make_data(cfg)``
    the configuration's data set, made on the device (anything the
    family's other functions take as ``data``).
``build_engine(cfg, traffic, data, seed, chips)``
    an engine built through the program's entry point for engine seed
    ``seed`` on ``chips`` chips; the harness calls its ``run()``, whose
    result ``res`` holds ``final_models``.
``summary(res, cfg, traffic)``
    one training as a dict: ``rounds``, ``certs``, ``accepted`` (a
    window's training has to repeat its engine's warm-up in these
    three), ``failed`` (bool) and ``examples``, the examples the
    training processed, counted as the family defines an example.
``observed(res)``
    what the training produced, as host arrays, for ``compare``.
``reference(cfg, traffic, data, seed)``
    the plain reference following the training of engine seed ``seed``:
    a dict with ``rounds``, and ``exact_until`` where the family's
    reference can stop being exact (None: exact throughout).
``compare(prog, ref, traffic)``
    the numbers compared, keyed as ``bench/limits/<config>.json`` keys
    their limits; among them ``decisions_differ``, to which the harness
    adds each window training that does not repeat its engine's warm-up.

A run makes the configuration's data set, draws the engine's seed from
``--seed`` (the traffic's ``engine_seeds`` of them, one by default),
builds an engine for each, warms each up with one whole training (the
compile, counted as set-up), then runs trainings back to back, the
engines in turn, until ``--seconds`` have passed and the turn is whole.
With ``--trace 1`` that window is traced and the per-layer metrics are
reported instead of the end-to-end ones. Once the window has closed and
the device's peak memory has been read, the family's reference follows
the window's last training and its comparison decides ``correct``.

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


def load_module(path: Path, name: str):
    """The module at ``path``; its directory joins ``sys.path`` so it can
    import its neighbours."""
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(kind: str, name: str):
    return load_module(BENCH / kind / f"{name}.py", f"bench_{kind}_{name}").read


def load_family(name: str | None, root: Path = BENCH):
    """The worker family ``name``: the module ``<root>/families/<name>.py``."""
    if not name:
        raise SystemExit("bench: the configuration names no worker family (its key `family`)")
    path = root / "families" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: unknown worker family {name!r}: no file {path}")
    return load_module(path, f"bench_families_{name}")


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------


def one_training(engine):
    """One whole training through the engine's public ``run()``; returns
    its result and its host-clock seconds."""
    import jax

    t0 = time.perf_counter()
    res = engine.run()
    jax.block_until_ready(res.final_models)
    return res, time.perf_counter() - t0


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

    family = spec.get("family") or load_family(cfg.get("family"))

    # the traffic's ``engine_seeds`` engines, each warmed by one whole
    # training; the window runs their trainings in turn
    eseeds = [engine_seed(seed, k) for k in range(int(traffic.get("engine_seeds", 1)))]
    data = jax.block_until_ready(family.make_data(cfg))
    engines, firsts, warm_s = [], [], []
    for es in eseeds:
        engines.append(family.build_engine(cfg, traffic, data, es, chips))
        warm, dt = one_training(engines[-1])
        firsts.append(family.summary(warm, cfg, traffic))
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
            trainings.append(dict(family.summary(last, cfg, traffic), seconds=dt, engine=k))
        # the window closes on a whole turn, so every engine seed weighs
        # the same in every run and no run stops early for a slow training
        if k == len(engines) - 1 and time.perf_counter() - t_window >= seconds:
            break
    window_s = time.perf_counter() - t_window
    if trace:
        jax.profiler.stop_trace()
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    log(f"bench: {len(trainings)} trainings in {window_s:.3f} s; rounds {trainings[-1]['rounds']}; "
        f"best certificate {min(trainings[-1]['certs']):.6f}")
    log("bench: training seconds " + " ".join(f"{t['engine']}:{t['seconds']:.4f}" for t in trainings))

    prog = family.observed(last)
    del last, engines
    gc.collect()

    # --- correct: the plain reference follows the same training ---
    t_ref = time.perf_counter()
    ref = family.reference(cfg, traffic, data, eseeds[trainings[-1]["engine"]])
    numbers = family.compare(prog, ref, traffic)
    decided = lambda t: (t["rounds"], t["certs"], t["accepted"])
    repeats = [t for t in trainings if decided(t) != decided(firsts[t["engine"]])]
    numbers["decisions_differ"] += len(repeats)
    log(f"bench: reference {time.perf_counter() - t_ref:.3f} s, {ref['rounds']} rounds, "
        f"exact until round {ref.get('exact_until')}")
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
