"""The Sparrow boosting family: ``BatchedSparrowWorker`` on the splice-site
stand-in data, followed by the plain reference of ``bench/reference.py``
and compared by ``bench/compare.py``.

An example is one row of a worker's in-memory sample scanned in a round:
each of the W workers scans ``min(chunk_size, sample_size)`` rows a
round, so a training of ``rounds`` rounds processes
``W x min(chunk_size, sample_size) x rounds`` examples.
"""

from __future__ import annotations

import math

import reference as splice_reference
from compare import compare as compare_runs
import splice_data


def make_data(cfg: dict):
    """The training split, ``(xtr, ytr)``; the held-out split is not used."""
    return splice_data.make_data(cfg)[:2]


def build_engine(cfg: dict, traffic: dict, data, seed: int, chips: int):
    """The program's own entry point, with every setting the cell
    depends on given explicitly (``EngineConfig`` otherwise reads
    ``REPRO_*`` environment variables)."""
    from repro.boosting import BatchedSparrowWorker, SparrowConfig
    from repro.boosting.scanner import ScannerConfig
    from repro.core.engine import EngineConfig, make_engine

    xtr, ytr = data
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


def summary(res, cfg: dict, traffic: dict) -> dict:
    import numpy as np

    rows = min(cfg["scanner"]["chunk_size"], cfg["sparrow"]["sample_size"])
    certs = np.asarray(res.final_certificates, np.float64)
    best = float(certs.min())
    reached = traffic["kind"] != "to_target" or best <= float(np.float32(traffic["target_certificate"]))
    return {
        "rounds": int(res.rounds),
        "certs": certs.tolist(),
        "accepted": int(res.messages_accepted),
        "failed": (not math.isfinite(best)) or not reached,
        "examples": cfg["engine"]["n_workers"] * rows * int(res.rounds),
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


def reference(cfg: dict, traffic: dict, data, seed: int) -> dict:
    """The plain reference following the training of engine seed ``seed``."""
    xtr, ytr = data
    to_target = traffic["kind"] == "to_target"
    return splice_reference.train(
        xtr, ytr, seed, splice_reference.params_from_config(cfg),
        max_rounds=traffic["max_rounds"] if to_target else traffic["rounds"],
        target=traffic["target_certificate"] if to_target else None,
    )


def compare(prog: dict, ref: dict, traffic: dict) -> dict[str, float]:
    return compare_runs(prog, ref, history=traffic["record_history"])
