"""A TMSN-SGD worker family for the harness's own tests.

``BatchedSGDWorker`` (``repro.core.sgd_worker.lm_sgd_worker``) trains a
registry transformer at its ``reduced()`` size with AdamW on the
synthetic token stream, under the same ``make_engine`` entry point the
Sparrow family drives. It lives only among the test files and reaches
the harness through ``run.load_family(name, root=...)``.

An example is one training sequence consumed by an optimizer step: each
of the W workers takes ``local_steps`` steps of ``batch_size`` sequences
a round.

The reference is the program's own synchronous oracle
(``repro.core.tmsn_sgd.oracle_run``: dense delay-1 exchange, uniform
speed), not an independent implementation: enough to test the harness's
plumbing, not a reference for a benchmark cell of this family.
"""

from __future__ import annotations

import math


def _worker(cfg: dict):
    from repro.configs import get_config, reduced
    from repro.core.sgd_worker import lm_sgd_worker
    from repro.core.tmsn_sgd import TMSNSGDConfig
    from repro.optim import AdamWConfig

    s = cfg["sgd"]
    return lm_sgd_worker(
        reduced(get_config(cfg["model"]["arch"])),
        AdamWConfig(lr=s["lr"]),
        TMSNSGDConfig(local_steps=s["local_steps"], ema=s["ema"], width_coef=s["width_coef"]),
        batch_size=s["batch_size"],
        seq=s["seq"],
    )


def make_data(cfg: dict):
    """None: each worker draws its token batches from its own key."""
    return None


def build_engine(cfg: dict, traffic: dict, data, seed: int, chips: int):
    from repro.core.engine import EngineConfig, make_engine

    e = cfg["engine"]
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_worker_mesh

        mesh = make_worker_mesh(chips)
    config = EngineConfig(
        n_workers=e["n_workers"],
        eps=e["eps"],
        max_rounds=traffic["rounds"],
        delay_rounds=e["delay_rounds"],
        seed=seed,
        record_history=traffic["record_history"],
        rounds_per_dispatch=e["rounds_per_dispatch"],
        fault_spec="",
        mesh=mesh,
    )
    return make_engine(_worker(cfg), config)


def summary(res, cfg: dict, traffic: dict) -> dict:
    certs = [float(c) for c in res.final_certificates]
    s = cfg["sgd"]
    return {
        "rounds": int(res.rounds),
        "certs": certs,
        "accepted": int(res.messages_accepted),
        "failed": not all(math.isfinite(c) for c in certs),
        "examples": cfg["engine"]["n_workers"] * s["local_steps"] * s["batch_size"] * int(res.rounds),
    }


def observed(res) -> dict:
    import numpy as np

    return {"rounds": int(res.rounds), "certs": np.asarray(res.final_certificates, np.float64)}


def reference(cfg: dict, traffic: dict, data, seed: int) -> dict:
    import numpy as np

    from repro.core.tmsn_sgd import oracle_run

    e = cfg["engine"]
    orc = oracle_run(_worker(cfg), e["n_workers"], traffic["rounds"], eps=e["eps"], seed=seed)
    return {"rounds": int(orc.rounds), "certs": np.asarray(orc.certs, np.float64)}


def compare(prog: dict, ref: dict, traffic: dict) -> dict[str, float]:
    import numpy as np

    gap = np.abs(prog["certs"] - ref["certs"]) / np.maximum(np.abs(ref["certs"]), 1e-3)
    return {
        "decisions_differ": float(prog["rounds"] != ref["rounds"]),
        "cert_gap": float(gap.max()),
    }
