"""No round-step ``lax.cond`` carries the workers' sample bins.

A ``lax.cond`` makes its outputs of every leaf one branch writes and the
other passes through; on the TPU the passing branch then copies the leaf.
For the Sparrow workers that leaf was the (W, m, d) sample bins, 1.8 GB at
the benchmark's size, copied in every round by the adoption cond, the
resample cond and the to-target freeze cond. These tests pin the
structure that avoids it, and that the restructured resample and scan
window compute exactly what the former formulations did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.boosting import BatchedSparrowWorker, SparrowConfig
from repro.boosting.scanner import ScannerConfig, window_rows
from repro.core import telemetry
from repro.core.engine import EngineConfig, TMSNEngine
from repro.core.tmsn_sgd import oracle_run
from repro.core.worker import masked_rows
from repro.data.splice import SpliceConfig, make_splice_like, train_test_split


@pytest.fixture(scope="module")
def data():
    xb, y, _ = make_splice_like(SpliceConfig(n=4_000, d=12, num_bins=8, seed=3))
    return train_test_split(xb, y)


def _worker(data, w, **kw):
    xtr, ytr, _, _ = data
    cfg = SparrowConfig(
        sample_size=256,
        capacity=16,
        scanner=ScannerConfig(chunk_size=96, num_bins=8, gamma0=0.25),
        n_workers=w,
        **kw,
    )
    return BatchedSparrowWorker(xtr, ytr, cfg)


def _engine(data, **kw):
    conf = dict(n_workers=4, max_rounds=8, seed=0, rounds_per_dispatch=4, fault_spec="",
                inflight_capacity=8)
    conf.update(kw)
    return TMSNEngine(_worker(data, 4), EngineConfig(**conf))


def _conds(jaxpr):
    """Every ``cond`` equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _conds(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _conds(sub)


def _big_avals(state):
    ws = state.worker
    return {str(jax.typeof(ws.sample.xb)), str(jax.typeof(ws.disk_margin))}


def _conds_carrying(jaxpr, avals):
    return [
        [str(v.aval) for v in e.outvars if str(v.aval) in avals] for e in _conds(jaxpr)
    ]


class TestNoCondCarriesTheBins:
    def test_round_step(self, data):
        eng = _engine(data)
        state = eng._init_state()
        jaxpr = jax.make_jaxpr(eng._round_step)(state).jaxpr
        # the adoption cond stays (it skips the adoption's work), but
        # forwards what it does not write
        assert list(_conds(jaxpr))
        assert not any(_conds_carrying(jaxpr, _big_avals(state)))

    def test_to_target_chunk(self, data):
        eng = _engine(data, target_certificate=-0.05, record_history=True)
        state = eng._init_state()
        jaxpr = jax.make_jaxpr(
            lambda st: eng._chunk_rounds(eng._round_step, jnp.any, st, 4)
        )(state).jaxpr
        assert not any(_conds_carrying(jaxpr, _big_avals(state)))


class TestMaskedRows:
    def test_a_leaf_left_alone_comes_back_as_is(self):
        take = jnp.array([True, False, True])
        kept = jnp.arange(6.0).reshape(3, 2)
        old = {"kept": kept, "written": jnp.zeros((3,))}
        new = {"kept": kept, "written": jnp.ones((3,))}
        out = masked_rows(take, new, old)
        assert out["kept"] is kept
        np.testing.assert_array_equal(out["written"], [1.0, 0.0, 1.0])


class TestWindowRows:
    @pytest.mark.parametrize("pos", [0, 1, 100, 159, 160, 161, 200, 255])
    def test_equals_the_row_gather(self, pos):
        m, c = 256, 96
        xb = jnp.arange(m * 5, dtype=jnp.int32).reshape(m, 5)
        want = xb[(pos + jnp.arange(c)) % m]
        got = jax.jit(window_rows, static_argnums=2)(xb, jnp.int32(pos), c)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize(
        "pos", [[0, 5, 100, 160, 161, 200, 255, 17], [3] * 8], ids=["some-wrap", "none-wrap"]
    )
    def test_batched_chunk_rows_equal_the_gather(self, scanned, pos):
        worker, state = scanned
        m, c = 256, 96
        state = state._replace(scanner=state.scanner._replace(pos=jnp.array(pos, jnp.int32)))
        want = jax.vmap(lambda x, p: x[(p + jnp.arange(c)) % m])(state.sample.xb, state.scanner.pos)
        np.testing.assert_array_equal(jax.jit(worker._chunk_rows)(state), want)


W8 = 8


def _fori_resample(worker, state, do):
    """The former formulation: every worker in turn, a cond per worker,
    and every worker's slice written back."""

    def one(i, carry):
        states, costs = carry
        st = jax.tree_util.tree_map(lambda a: a[i], states)
        new, cost = jax.lax.cond(
            do[i], worker._resample_one, lambda s: (s, jnp.zeros((), jnp.float32)), st
        )
        states = jax.tree_util.tree_map(lambda a, v: a.at[i].set(v), states, new)
        return states, costs.at[i].set(cost)

    costs = jnp.zeros(do.shape, jnp.float32)
    return jax.lax.fori_loop(0, do.shape[0], one, (state, costs))


@pytest.fixture(scope="module")
def scanned(data):
    """Eight workers a few segments in, one with its disk margins marked
    stale by an adoption."""
    worker = _worker(data, W8)
    state = worker.init_batch(W8, 0)
    scan = jax.jit(worker.scan_round)
    for _ in range(12):
        state, _, _ = scan(state, jnp.ones((W8,), bool))
    assert int(state.model.count.sum()) > 0
    return worker, state._replace(disk_t=state.disk_t.at[5].set(-1))


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestResampleRound:
    def test_nobody_resamples(self, scanned):
        worker, state = scanned
        new, cost = jax.jit(worker.resample_round)(state, jnp.zeros((W8,), bool))
        _assert_trees_equal(new, state)
        np.testing.assert_array_equal(cost, np.zeros(W8, np.float32))

    def test_some_workers_match_the_former_loop(self, scanned):
        worker, state = scanned
        do = jnp.zeros((W8,), bool).at[1].set(True).at[5].set(True)
        got = jax.jit(worker.resample_round)(state, do)
        want = jax.jit(lambda s, d: _fori_resample(worker, s, d))(state, do)
        _assert_trees_equal(got, want)
        new, cost = got
        np.testing.assert_array_equal(np.asarray(new.resamples - state.resamples),
                                      np.asarray(do, np.int32))
        assert np.all(np.asarray(cost)[[1, 5]] > 0)

    def test_engine_run_through_resamples_matches_the_oracle(self, data):
        """An ESS threshold of 1 resamples after every fire: the engine,
        which now calls the resample hook with no guard, stays
        bit-identical to the synchronous oracle, and the run's counters
        read the resamples and adoptions that happened."""
        worker = _worker(data, 4, ess_threshold=1.0)
        rounds = 24
        orc = oracle_run(worker, 4, rounds, eps=0.0, seed=0)
        res = TMSNEngine(
            worker,
            EngineConfig(n_workers=4, eps=0.0, max_rounds=rounds, delay_rounds=1, seed=0,
                         fault_spec=""),
        ).run()
        np.testing.assert_array_equal(
            np.asarray(res.final_certificates, np.float32), orc.certs
        )
        resamples = int(np.sum(np.asarray(orc.state.resamples)))
        assert resamples >= 4
        counters = telemetry.runs(last=1)[0].counters
        assert counters["resamples"] == resamples
        assert counters["adoptions"] == res.messages_accepted
