"""Benchmark entry point — one module per paper table/figure plus the
framework-level benches. Prints ``name,value,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run [--full|--tiny] [--json out.json]

(--full runs the paper-scale sizes; default is the quick profile so the
suite completes on the CPU container; --tiny runs only the
minutes-not-hours benches — the every-push ``bench-smoke`` CI tier that
keeps a results artifact on every commit. --json additionally writes
the collected ``{name: value}`` dict as machine-readable JSON — the
format CI artifacts and the BENCH_*.json trajectory share. The JSON
carries a ``_schema`` entry with a format version, the machine shape
(device count, backend), the bench profile, and the git SHA the run
measured, so the regression guard and trajectory plots can key on
comparable runs; metric keys never start with ``_``.)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

#: the --tiny selection: benches that finish in ~seconds on a 2-core
#: runner (still real measurements — stopping rule, kernel microbench,
#: protocol counters, the chaos resilience section, the serving tier's
#: continuous-batching + adoption run) so every push gets a comparable
#: JSON artifact
TINY_BENCHES = ["stopping", "kernels", "protocol", "tmsn_sgd", "chaos", "serving"]


def _git_sha() -> str | None:
    """SHA of the tree the numbers were measured on (None outside git —
    e.g. a source tarball; the artifact is still valid, just unpinned)."""
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = proc.stdout.strip()
    return sha if proc.returncode == 0 and sha else None


def _parse_value(raw: str):
    try:
        return float(raw)
    except ValueError:
        return raw


def collect(selected: list[str], benches: dict, quick: bool) -> tuple[dict, int]:
    """Run the selected benches, printing (flushed) each CSV line as it
    is produced — a hung bench still leaves partial output in CI logs.
    Returns (results_dict, failures)."""
    results: dict = {}
    failures = 0

    def _emit(line: str) -> None:
        print(line, flush=True)

    for name in selected:
        t0 = time.time()
        try:
            for line in benches[name](quick=quick):
                _emit(line)
                parts = line.split(",")
                if len(parts) >= 2:
                    results[parts[0]] = _parse_value(parts[1])
            wall = time.time() - t0
            _emit(f"bench.{name}.wall_s,{wall:.1f},")
            results[f"bench.{name}.wall_s"] = round(wall, 1)
        except Exception as e:  # noqa: BLE001
            failures += 1
            _emit(f"bench.{name}.FAILED,{type(e).__name__},{e}")
            results[f"bench.{name}.FAILED"] = type(e).__name__
    return results, failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--tiny", action="store_true",
        help=f"run only the fast benches ({','.join(TINY_BENCHES)}) — the "
        "every-push bench-smoke profile",
    )
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument(
        "--json", default=None, metavar="OUT", help="also write {name: value} JSON here"
    )
    args = ap.parse_args()
    if args.full and args.tiny:
        ap.error("--full and --tiny are mutually exclusive")
    quick = not args.full

    from repro.launch.runtime import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_convergence,
        bench_kernels,
        bench_protocol,
        bench_scaling,
        bench_stopping,
    )

    benches = {
        "stopping": bench_stopping.run,
        "kernels": bench_kernels.run,
        "protocol": bench_protocol.run,
        "convergence": bench_convergence.run,
        "scaling": bench_scaling.run,
        "chaos": bench_scaling.run_chaos,
    }
    try:
        from benchmarks import bench_tmsn_sgd

        benches["tmsn_sgd"] = bench_tmsn_sgd.run
    except ImportError:
        pass
    try:
        from benchmarks import bench_ablations

        benches["ablations"] = bench_ablations.run
    except ImportError:
        pass
    try:
        from benchmarks import bench_serving

        benches["serving"] = bench_serving.run
    except ImportError:
        pass

    if args.only:
        selected = args.only.split(",")
    elif args.tiny:
        selected = list(TINY_BENCHES)
    else:
        selected = list(benches)
    print("name,value,derived", flush=True)
    results, failures = collect(selected, benches, quick)
    if args.json:
        import jax

        payload = {
            "_schema": {
                "version": 3,
                "devices": jax.device_count(),
                "backend": jax.default_backend(),
                "profile": "full" if args.full else ("tiny" if args.tiny else "quick"),
                "git_sha": _git_sha(),
            },
            **results,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {len(results)} results to {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
