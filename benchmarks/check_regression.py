"""Benchmark regression guard.

Compares a ``bench_results.json`` run (the output of
``python -m benchmarks.run --json``) against the committed
``benchmarks/baseline.json`` and exits non-zero when a guarded metric
regresses past its tolerance — the full CI tier *fails* on a real
slowdown instead of silently uploading artifacts.

  python -m benchmarks.check_regression bench_results.json
  python -m benchmarks.check_regression --write-baseline bench_results.json

Baseline schema::

  {
    "schema_version": 1,
    "metrics": {
      "scaling.w8.rounds_to_target": {"value": 21, "tolerance": 0.2},
      ...
    }
  }

Every guarded metric is lower-is-better; a run fails when
``current > value * (1 + tolerance * scale)``. Metrics present in only
one of baseline/current (a guarded metric missing from the results, or
a guardable result not yet baselined) WARN instead of failing — newly
added benchmark metrics and baseline entries can land in either order
without breaking the other side's CI; rebaseline to re-tighten
coverage. Protocol
metrics (rounds-to-target, gossip bytes) get the tight 20% tolerance;
wall-clock metrics carry a wider default (+55 points) because the
baseline machine and the CI runner differ — rebaseline from a CI
artifact (download ``bench-results``, re-run with ``--write-baseline
--wall-clock-extra 0``) to drop wall clock to the tight 20% guard.
``--tolerance-scale`` scales every tolerance at once (an escape hatch
for known-noisy runners; 1.0 in CI). Runs are only compared on the
machine shape they were baselined on: the results' ``_schema`` must
match the baseline's recorded ``source`` or the guard refuses.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baseline.json")

#: metrics the baseline snapshots, with per-pattern tolerances
#: (lower-is-better for every one of them)
GUARDED = [
    ("scaling.w*.rounds_to_target", 0.20),
    ("scaling.w*.wall_ms_per_round", 0.20),
    # matches both the dense `sharded_wN` and the `sharded_wN_gated`
    # variants (gossip bytes are exact per mode, so the tight guard
    # catches any accounting or gating regression)
    ("scaling.sharded_w*.wall_ms_per_round", 0.20),
    ("scaling.sharded_w*.gossip_bytes_per_round", 0.20),
    ("scaling.dispatch_w*.wall_ms_per_round", 0.20),
    # sparse pending-queue sweeps (uniform, het-delay, and the capped
    # W=4096 run dense cannot complete)
    ("scaling.sparse_w*.wall_ms_per_round", 0.20),
    # control-plane sweep (dense certs/flags vs top-k triples): the
    # byte figures are exact formulas, so the tight guard catches any
    # control-accounting regression; wall clock gets the usual headroom
    ("scaling.ctrl_w*.wall_ms_per_round", 0.20),
    ("scaling.ctrl_w*.control_bytes_per_round", 0.20),
    # hierarchical (pod, workers) mesh: per-tier footprints are exact
    # formulas (any drift is an accounting regression), wall clock gets
    # the usual cross-machine headroom until rebaselined
    ("scaling.pod2_w*.wall_ms_per_round", 0.20),
    ("scaling.pod2_w*.ici_bytes_per_round", 0.20),
    ("scaling.pod2_w*.dcn_bytes_per_round", 0.20),
    # engine-hosted TMSN-SGD (bench_tmsn_sgd.py, --tiny tier): protocol
    # metrics on fixed seeds — WARN until the baseline is regenerated
    # with them, then guarded like the scaling suite
    ("tmsn_sgd.engine_rounds_to_target", 0.20),
    ("tmsn_sgd.engine_bytes_broadcast", 0.20),
    # chaos resilience section (bench_scaling.run_chaos, --tiny tier):
    # the injected/rejected counters are deterministic on the seeded
    # fault plan (drift means the counter-hash or fault accounting
    # changed) and the cert-gap-vs-clean figures are 0.0 at the pinned
    # rates (any nonzero gap after baselining is a resilience
    # regression). WARN until the baseline is regenerated with them
    ("chaos.*_w*.wall_ms_per_round", 0.20),
    ("chaos.*.messages_dropped_injected", 0.20),
    ("chaos.*.messages_corrupt_rejected", 0.20),
    ("chaos.*.best_cert_gap_vs_clean", 0.20),
    # serving tier (bench_serving.py, --tiny tier): request latency and
    # per-step wall get the wall-clock headroom via the name check; the
    # zero-downtime counters baseline at 0, so ANY nonzero reading is a
    # hard failure once baselined; the stale-cert gaps are
    # deterministic on the seeded engine run. Higher-is-better
    # throughput (req_per_s, decode_tok_per_s) is reported but not
    # guarded — the guard is one-sided lower-is-better. WARN until the
    # baseline is regenerated with them
    ("serving.b*.latency_p50_wall_ms", 0.20),
    ("serving.b*.latency_p99_wall_ms", 0.20),
    ("serving.b*.step_p50_wall_ms", 0.20),
    ("serving.adopt.dropped_requests", 0.20),
    ("serving.adopt.recompiles", 0.20),
    ("serving.adopt.blip_p99_wall_ms", 0.20),
    ("serving.adopt.steady_p99_wall_ms", 0.20),
    ("serving.adopt.stale_cert_gap_mean", 0.20),
    ("serving.adopt.stale_cert_gap_max", 0.20),
]

#: wall-clock metrics absorb cross-machine noise until rebaselined from
#: a CI artifact; protocol metrics stay at the tight default
WALL_CLOCK_EXTRA = 0.55  # 0.20 + 0.55 = 75% headroom


def _tolerance_for(name: str, wall_clock_extra: float) -> float | None:
    for pattern, tol in GUARDED:
        if fnmatch.fnmatch(name, pattern):
            if "wall_ms" in name or "_us" in name or "wall_s" in name:
                return tol + wall_clock_extra
            return tol
    return None


def write_baseline(results: dict, path: str, wall_clock_extra: float) -> int:
    metrics = {}
    for name, value in sorted(results.items()):
        if name.startswith("_") or not isinstance(value, (int, float)):
            continue
        tol = _tolerance_for(name, wall_clock_extra)
        if tol is not None:
            metrics[name] = {"value": value, "tolerance": tol}
    schema = results.get("_schema", {})
    source = {k: schema.get(k) for k in ("devices", "backend", "profile")}
    # the RESULTS format version (and the SHA the numbers came from):
    # lets check() flag a cross-version comparison instead of silently
    # comparing metrics whose semantics may have shifted between formats
    source["results_version"] = schema.get("version")
    source["git_sha"] = schema.get("git_sha")
    with open(path, "w") as f:
        json.dump(
            {
                "schema_version": 1,
                "source": source,
                "metrics": metrics,
            },
            f,
            indent=1,
            sort_keys=True,
        )
        f.write("\n")
    print(f"wrote {len(metrics)} guarded metrics to {path}")
    return 0


def check(results: dict, baseline: dict, scale: float) -> int:
    failures = []
    warnings = []
    checked = 0
    # numbers are only comparable on the same machine shape and bench
    # profile — that is what the results' _schema / baseline's source
    # record. A mismatch means "rebaseline", not "regression".
    schema = results.get("_schema", {})
    source = baseline.get("source", {})
    for key in ("devices", "backend", "profile"):
        if source.get(key) is not None and schema.get(key) != source.get(key):
            print(
                f"machine-shape mismatch on '{key}': results {schema.get(key)!r} "
                f"vs baseline {source.get(key)!r} — these runs are not comparable.\n"
                "Rebaseline on this shape with: python -m benchmarks.check_regression "
                "--write-baseline <results.json>"
            )
            return 1
    # same machine shape but a different results-format version: the
    # metrics MAY have shifted meaning between formats, so say so out
    # loud instead of silently comparing (shape matches, so a comparison
    # is still more useful than a refusal — rebaseline to clear this)
    if schema.get("version") != source.get("results_version"):
        print(
            f"WARN: results schema version {schema.get('version')!r} differs from "
            f"the baseline's recorded {source.get('results_version')!r} on a "
            "matching machine shape — comparing anyway, but metric semantics may "
            "have changed between formats; rebaseline with --write-baseline to "
            "clear this warning"
        )
    for name, spec in sorted(baseline["metrics"].items()):
        base_value, tol = spec["value"], spec["tolerance"] * scale
        current = results.get(name)
        if current is None or not isinstance(current, (int, float)):
            # one-sided metric: warn, don't fail — a bench rename or a
            # not-yet-rerun bench shouldn't block unrelated changes
            warnings.append(f"  baseline-only {name} (baseline {base_value:g})")
            continue
        checked += 1
        allowed = base_value * (1.0 + tol)
        status = "FAIL" if current > allowed else "ok"
        print(
            f"  {status:7s}  {name}: {current:g} vs baseline {base_value:g} "
            f"(allowed <= {allowed:g})"
        )
        if current > allowed:
            failures.append(
                f"  REGRESSED {name}: {current:g} > {allowed:g} "
                f"({100 * (current / base_value - 1):+.0f}% vs +{100 * tol:.0f}% allowed)"
            )
    # the other side: guardable metrics in the results with no baseline
    # entry yet — also warn-only, with a pointer at the fix
    for name, value in sorted(results.items()):
        if name.startswith("_") or not isinstance(value, (int, float)):
            continue
        if name not in baseline["metrics"] and _tolerance_for(name, 0.0) is not None:
            warnings.append(f"  current-only  {name} ({value:g}) — not guarded yet")
    print(f"checked {checked}/{len(baseline['metrics'])} guarded metrics")
    if warnings:
        print("\nWARN: metrics present in only one of baseline/current "
              "(rebaseline with --write-baseline to re-tighten coverage):")
        for line in warnings:
            print(line)
    if failures:
        print("\nbenchmark regression guard FAILED:")
        for line in failures:
            print(line)
        return 1
    print("benchmark regression guard passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("results", help="bench_results.json from benchmarks.run --json")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--write-baseline", action="store_true",
                    help="snapshot the guarded metrics of RESULTS as the new baseline")
    ap.add_argument("--tolerance-scale", type=float, default=1.0)
    ap.add_argument(
        "--wall-clock-extra", type=float, default=WALL_CLOCK_EXTRA,
        help="extra tolerance baked into wall-clock metrics at baseline-write "
        "time; pass 0 when rebaselining from the SAME machine the guard runs "
        "on (e.g. a CI artifact) to get the tight 20%% wall-clock guard",
    )
    args = ap.parse_args()

    with open(args.results) as f:
        results = json.load(f)
    if args.write_baseline:
        return write_baseline(results, args.baseline, args.wall_clock_extra)
    with open(args.baseline) as f:
        baseline = json.load(f)
    if baseline.get("schema_version") != 1:
        print(f"unknown baseline schema_version: {baseline.get('schema_version')}")
        return 1
    return check(results, baseline, args.tolerance_scale)


if __name__ == "__main__":
    sys.exit(main())
