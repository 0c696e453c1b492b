"""The `correct` check at a size a test run can hold, on the CPU.

A run of a small cell goes through the whole harness (data, engine,
window, reference, comparison) with the look for a chip skipped. The
sound program comes out correct; the bfloat16 control and each fault
planted under the timed path come out not correct.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import run
from control import control_numbers
from splice_data import make_data

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 11


def small_spec(kind: str, chips: int = 1) -> dict:
    """A cell of the ``splice-w16`` configuration cut to a test's size:
    250k rows at the published width and 4 workers per chip, each with
    the cell's own sample and chunk, so the committed traffic file of
    ``kind`` runs the same passes, fires and adoptions."""
    with open(os.path.join(BENCH, "configs", "splice-w16.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "limits", "splice-w16.json")) as f:
        limits = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{kind.replace('_', '-')}.json")) as f:
        traffic = json.load(f)
    cfg["data"].update(n=250000)
    cfg["engine"].update(n_workers=4 * chips, inflight_capacity=8 * chips)
    return {
        "cell": {"name": f"small.{kind}", "config": "small", "traffic": kind, "chips": chips},
        "config": cfg,
        "traffic": traffic,
        "limits": limits,
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }


def run_small(spec: dict) -> dict:
    jax.clear_caches()  # a planted fault must be traced afresh
    return run.run_cell(spec["cell"]["name"], SEED, 0.2, False, spec=spec, require_chip=False)


@pytest.mark.parametrize("kind", ["to_target", "rounds"])
def test_sound_program_is_correct(kind):
    res = run_small(small_spec(kind))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["decisions_differ"]["value"] == 0


@pytest.mark.parametrize("kind", ["to_target", "rounds"])
def test_bfloat16_control_is_rejected(kind):
    spec = small_spec(kind)
    data = make_data(spec["config"])
    numbers = control_numbers(spec["config"], spec["traffic"], data, run.engine_seed(SEED))
    assert any(v > spec["limits"][k] for k, v in numbers.items()), numbers


def _frozen_step(monkeypatch):
    """The worker's step returns its state unchanged."""
    from repro.boosting.batched_sparrow import BatchedSparrowWorker

    def scan_round(self, state, mask):
        w = mask.shape[0]
        return state, jnp.zeros((w,), jnp.float32), jnp.zeros((w,), bool)

    monkeypatch.setattr(BatchedSparrowWorker, "scan_round", scan_round)


def _half_batch(monkeypatch):
    """The histogram scans half of each chunk and doubles it (the mean
    taken over the rest)."""
    from repro.kernels import ops

    orig = ops.edge_scan

    def half(xb, wy, w, *, num_bins, tile_n=512, interpret=None):
        h = xb.shape[0] // 2
        hist, W, V, T = orig(xb[:h], wy[:h], w[:h], num_bins=num_bins,
                             tile_n=min(tile_n, h), interpret=interpret)
        return 2.0 * hist, 2.0 * W, 2.0 * V, 2.0 * T

    monkeypatch.setattr(ops, "edge_scan", half)


def _altered_answer(monkeypatch):
    """Each fired stump's threshold is altered where it is produced."""
    from repro.boosting import batched_sparrow

    orig = batched_sparrow.append_stump

    def append(model, feat, thr, sign, alpha):
        return orig(model, feat, (thr + 1) % 3, sign, alpha)

    monkeypatch.setattr(batched_sparrow, "append_stump", append)


def _no_exchange(monkeypatch):
    """The gossip all-gather moves nothing between chips: every device
    sees its own workers' entries in every device's place."""
    orig = jax.lax.all_gather

    def local(x, axis_name, *, axis=0, tiled=False, **kw):
        n = jax.lax.axis_size(axis_name)
        if tiled:
            return jax.tree_util.tree_map(lambda a: jnp.concatenate([a] * n, axis=axis), x)
        return jax.tree_util.tree_map(lambda a: jnp.stack([a] * n, axis=axis), x)

    monkeypatch.setattr(jax.lax, "all_gather", local)
    return orig


FAULTS = {
    "state_unchanged": (_frozen_step, 1),
    "half_batch": (_half_batch, 1),
    "altered_answer": (_altered_answer, 1),
    "no_exchange": (_no_exchange, 4),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_rejected(fault, monkeypatch):
    plant, chips = FAULTS[fault]
    if len(jax.devices()) < chips:
        pytest.fail(f"needs {chips} host devices; run this file by path so conftest sets them")
    spec = small_spec("to_target", chips)
    plant(monkeypatch)
    res = run_small(spec)
    assert not res["correct"], res["checks"]


def test_sharded_sound_program_is_correct():
    if len(jax.devices()) < 4:
        pytest.fail("needs 4 host devices; run this file by path so conftest sets them")
    res = run_small(small_spec("to_target", 4))
    assert res["correct"], res["checks"]

