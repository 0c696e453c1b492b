#!/usr/bin/env python3
"""The control of the `correct` check: the plain reference, computed with
its histogram weights in bfloat16, put in the program's place.

    python bench/control.py --workload splice-w16.to-target --seeds 1 2 3

It builds the cell's data once; for each seed it follows the training
with the float32 reference and with the bfloat16 one, compares the
second with the first exactly as a run compares the program, and
prints each number beside the cell's limit. The check is sound only
where the control comes out not correct. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import reference
from compare import compare


def as_observed(ref: dict, record_history: bool) -> dict:
    """A reference training in the shape of what the program reports."""
    w = len(ref["final_certificates"])
    return {
        "rounds": ref["rounds"],
        "history": ref["history"] if record_history else ref["history"][:w],
        "final_certificates": ref["final_certificates"],
        "models": ref["models"],
        "accepted": ref["accepted"],
        "evicted": 0,
    }


def control_numbers(cfg: dict, traffic: dict, data, engine_seed: int) -> dict[str, float]:
    """The compared numbers of the bfloat16 control against the float32
    reference on one data set."""
    xtr, ytr = data[:2]
    to_target = traffic["kind"] == "to_target"
    kw = dict(
        max_rounds=traffic["max_rounds"] if to_target else traffic["rounds"],
        target=traffic["target_certificate"] if to_target else None,
    )
    exact = reference.train(xtr, ytr, engine_seed, reference.params_from_config(cfg), **kw)
    low = reference.train(
        xtr, ytr, engine_seed, reference.params_from_config(cfg, low_precision=True), **kw
    )
    return compare(as_observed(low, traffic["record_history"]), exact,
                   history=traffic["record_history"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    import run
    from splice_data import make_data

    spec = run.load_cell(args.workload)
    cfg, traffic, limits = spec["config"], spec["traffic"], spec["limits"]
    failed_all = True
    data = jax.block_until_ready(make_data(cfg))
    for seed in args.seeds:
        numbers = control_numbers(cfg, traffic, data, run.engine_seed(seed))
        ok = all(math.isfinite(v) and v <= limits[k] for k, v in numbers.items())
        failed_all &= not ok
        print(json.dumps({"seed": seed, "control_correct": ok, "numbers": numbers}), flush=True)
    print(f"control: {'rejected on every seed' if failed_all else 'PASSED on some seed'}", file=sys.stderr)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
