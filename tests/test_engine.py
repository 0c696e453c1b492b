"""Tests for the vectorized round-based TMSN engine and the batched
Sparrow worker.

Equivalence strategy (DESIGN: the event sim is the fidelity-1 oracle):

  * protocol level — a deterministic toy worker runs under BOTH
    substrates on a uniform-speed, zero-latency config with the same
    seeds; final certificates (and message counters) must be identical;
  * computation level — the batched Sparrow worker must reproduce the
    unbatched ``SparrowWorker`` segment-for-segment (same RNG streams),
    including the resample path and the Pallas kernel scan path.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.boosting import BatchedSparrowWorker, SparrowConfig, SparrowWorker
from repro.boosting.batched_sparrow import common_prefix_len
from repro.boosting.scanner import ScannerConfig
from repro.boosting.sparrow import feature_ownership_masks
from repro.core.engine import EngineConfig, TMSNEngine, make_engine, quantize_latency
from repro.core.simulator import SimulatorConfig, TMSNSimulator, WorkerSpec
from repro.data.splice import SpliceConfig, make_splice_like, train_test_split


# ---------------------------------------------------------------------------
# Toy worker: fires every ``period[i]`` segments; its own certificate path
# after f fires is ``-dec[i] * f``; adoption takes the min. The final
# certificates depend only on which messages were delivered, so the toy
# pins the engine's gossip semantics against the event simulator.
# ---------------------------------------------------------------------------


class ToySimWorker:
    def __init__(self, period, dec):
        self.period = list(period)
        self.dec = list(dec)

    def init_state(self, worker_id, seed):
        return {"wid": worker_id, "segs": 0, "fires": 0, "cert": 0.0, "from": -1}

    def run_segment(self, s):
        s = dict(s)
        s["segs"] += 1
        fired = s["segs"] % self.period[s["wid"]] == 0
        if fired:
            s["fires"] += 1
            # float32 arithmetic so final certs are bit-identical to the
            # engine's array math
            own = float(-(np.float32(self.dec[s["wid"]]) * np.float32(s["fires"])))
            s["cert"] = min(s["cert"], own)
        return s, 1.0, fired

    def certificate(self, s):
        return s["cert"]

    def export_model(self, s):
        return {"owner": s["wid"], "cert": s["cert"]}

    def adopt(self, s, model, certificate):
        s = dict(s)
        s["cert"] = float(certificate)
        s["from"] = int(model["owner"])
        return s

    def payload_bytes(self, model):
        return 8


class ToyBatchedWorker:
    def __init__(self, period, dec):
        self.period = jnp.asarray(period, jnp.int32)
        self.dec = jnp.asarray(dec, jnp.float32)

    def init_batch(self, n_workers, seed):
        z = jnp.zeros((n_workers,), jnp.int32)
        return {
            "segs": z,
            "fires": z,
            "cert": jnp.zeros((n_workers,), jnp.float32),
            "from": jnp.full((n_workers,), -1, jnp.int32),
        }

    def scan_round(self, state, mask):
        segs = state["segs"] + mask.astype(jnp.int32)
        fired = mask & (segs % self.period == 0)
        fires = state["fires"] + fired.astype(jnp.int32)
        own = -self.dec * fires
        cert = jnp.where(fired, jnp.minimum(state["cert"], own), state["cert"])
        new = {"segs": segs, "fires": fires, "cert": cert, "from": state["from"]}
        return new, mask.astype(jnp.float32), fired

    def needs_resample(self, state):
        return jnp.zeros(state["cert"].shape, bool)

    def resample_round(self, state, do):
        return state, jnp.zeros(state["cert"].shape, jnp.float32)

    def certificates(self, state):
        return state["cert"]

    def export_models(self, state):
        w = state["cert"].shape[0]
        return {
            "owner": jnp.arange(w, dtype=jnp.int32),
            "cert": state["cert"],
            "adopted_from": state["from"],
        }

    def adopt_batch(self, state, models, certs, take):
        new = dict(state)
        new["cert"] = jnp.where(take, certs, state["cert"])
        new["from"] = jnp.where(take, models["owner"], state["from"])
        return new, jnp.zeros(state["cert"].shape, jnp.float32)

    def payload_bytes(self):
        return 8


class TestEngineSimulatorEquivalence:
    def test_single_sender_identical_final_certificates(self):
        """Uniform speeds, zero latency, same seeds: the engine and the
        event simulator must end on IDENTICAL final certificates."""
        w = 4
        period = [1, 10**9, 10**9, 10**9]
        dec = [0.1] * w
        target = -0.95

        sim = TMSNSimulator(
            ToySimWorker(period, dec),
            [WorkerSpec(speed=1.0) for _ in range(w)],
            SimulatorConfig(
                n_workers=w,
                base_latency=0.0,
                latency_jitter=0.0,
                target_certificate=target,
                max_events=10_000,
                seed=0,
            ),
        )
        res_sim = sim.run()

        eng = TMSNEngine(
            ToyBatchedWorker(period, dec),
            EngineConfig(
                n_workers=w, delay_rounds=1, target_certificate=target, max_rounds=500,
                # exact-accounting comparison against the fault-free event
                # simulator — the CI chaos leg must not inject here
                fault_spec="",
            ),
        )
        res_eng = eng.run()

        assert res_eng.final_certificates == res_sim.final_certificates
        # w0 needed 10 fires to cross the target; everyone saw its 9th
        np.testing.assert_allclose(
            res_sim.final_certificates, [-1.0, -0.9, -0.9, -0.9], atol=1e-6
        )
        assert res_eng.rounds == 10
        # message accounting matches too: 10 broadcasts x 3, 9 adoptions x 3
        assert res_eng.messages_sent == res_sim.messages_sent == 30
        assert res_eng.messages_accepted == res_sim.messages_accepted == 27
        assert res_eng.messages_discarded == res_sim.messages_discarded == 0
        # ring routing: every adopter took worker 0's model
        assert [int(m["adopted_from"]) for m in res_eng.final_models[1:]] == [0, 0, 0]

    def test_multi_sender_certs_converge(self):
        w = 8
        eng = TMSNEngine(
            ToyBatchedWorker([1] * w, [0.01 * (i + 1) for i in range(w)]),
            EngineConfig(n_workers=w, delay_rounds=1, max_rounds=50, fault_spec=""),
        )
        res = eng.run()
        certs = np.asarray(res.final_certificates)
        # fastest-decreasing worker (w-1) leads; everyone is within one
        # broadcast round of the global best
        assert certs.min() == pytest.approx(-0.08 * 50)
        assert certs.max() - certs.min() <= 0.08 * 2 + 1e-6
        assert res.messages_accepted > 0

    def test_link_delays_slow_convergence(self):
        w = 4
        mk = lambda d: TMSNEngine(
            ToyBatchedWorker([1, 10**9, 10**9, 10**9], [0.1] * w),
            EngineConfig(n_workers=w, delay_rounds=d, max_rounds=20, fault_spec=""),
        ).run()
        near = mk(1)
        far = mk(8)
        # same sender progress, but laggier links deliver older certs
        assert near.final_certificates[0] == far.final_certificates[0]
        assert max(far.final_certificates[1:]) > max(near.final_certificates[1:])

    def test_laggard_speed_vector(self):
        """A 0.25-speed worker completes ~1/4 of the segments (credit
        accumulator), mirroring the sim's cost/speed clock."""
        w = 3
        eng = TMSNEngine(
            ToyBatchedWorker([1] * w, [0.1] * w),
            EngineConfig(n_workers=w, speed=[1.0, 1.0, 0.25], max_rounds=40,
                         fault_spec=""),
        )
        res = eng.run()
        certs = np.asarray(res.final_certificates)
        assert certs[0] == pytest.approx(-4.0)
        # the laggard's own path only reached -1.0 but gossip kept it close
        assert certs[2] <= -3.8

    def test_fail_stop_mask(self):
        w = 4
        eng = TMSNEngine(
            ToyBatchedWorker([1, 10**9, 10**9, 10**9], [0.1] * w),
            EngineConfig(n_workers=w, fail_round=[5, 10**6, 10**6, 10**6], max_rounds=30,
                         fault_spec=""),
        )
        res = eng.run()
        # sender died after 5 rounds (4 completed segments + 1 dead round);
        # survivors keep its last delivered certificate, run doesn't stall
        assert res.final_certificates[0] == pytest.approx(-0.5)
        assert max(res.final_certificates[1:]) <= -0.4 + 1e-9
        assert res.rounds == 30

    def test_eps_gates_acceptance_not_broadcast(self):
        w = 3
        eng = TMSNEngine(
            ToyBatchedWorker([1, 10**9, 10**9], [0.01] * w),
            EngineConfig(n_workers=w, eps=0.5, max_rounds=20, fault_spec=""),
        )
        res = eng.run()
        assert res.messages_sent > 0  # broadcasts still go out
        assert res.messages_accepted == 0  # but the gap rejects them all
        assert res.messages_discarded > 0

    def test_quantize_latency(self):
        d = quantize_latency(0.05, 0.02, round_dt=0.01, n_workers=6, seed=0)
        assert d.shape == (6, 6)
        assert d.min() >= 1
        assert 4 <= d.max() <= 8  # (0.05..0.07)/0.01, rounded


# ---------------------------------------------------------------------------
# Dispatch chunking: K rounds per jitted lax.scan call must be a pure
# execution-substrate choice — final certificates, history, and exact
# rounds-to-target identical to the one-dispatch-per-round engine.
# ---------------------------------------------------------------------------


class TestChunkedDispatch:
    def _run(self, rpd, **cfg):
        w = 4
        return TMSNEngine(
            ToyBatchedWorker([1, 2, 10**9, 10**9], [0.1, 0.07, 0.1, 0.1]),
            EngineConfig(n_workers=w, rounds_per_dispatch=rpd, **cfg),
        ).run()

    def test_fixed_rounds_identical(self):
        """max_rounds not divisible by the chunk exercises the
        remainder chunk; certs, history, and counters must match."""
        runs = {rpd: self._run(rpd, max_rounds=21) for rpd in (1, 8, 21, 32)}
        base = runs[1]
        assert base.rounds == 21
        for rpd, res in runs.items():
            assert res.final_certificates == base.final_certificates, rpd
            assert res.history == base.history, rpd
            assert res.rounds == base.rounds, rpd
            assert res.messages_sent == base.messages_sent, rpd
            assert res.messages_accepted == base.messages_accepted, rpd

    def test_target_stop_mid_chunk_identical(self):
        """Crossing the target inside a chunk freezes the device state
        on the crossing round: exact rounds-to-target AND a final state
        identical to the unchunked run."""
        runs = {rpd: self._run(rpd, target_certificate=-0.95, max_rounds=500)
                for rpd in (1, 8)}
        assert runs[1].rounds == runs[8].rounds == 10
        assert runs[8].final_certificates == runs[1].final_certificates
        assert runs[8].history == runs[1].history
        assert runs[8].messages_sent == runs[1].messages_sent

    def test_rejects_bad_chunk(self):
        with pytest.raises(ValueError, match="rounds_per_dispatch"):
            TMSNEngine(
                ToyBatchedWorker([1], [0.1]),
                EngineConfig(n_workers=1, rounds_per_dispatch=0),
            )

    def test_sparrow_chunked_identical(self, small_data):
        """The real batched worker through chunked dispatch: same final
        certificates and history as one dispatch per round."""
        xtr, ytr, _, _ = small_data
        w = 3
        cfg = _cfg(w, sample_size=256, capacity=16,
                   scanner=ScannerConfig(chunk_size=128, num_bins=8, gamma0=0.25))
        runs = {}
        for rpd in (1, 4):
            eng = TMSNEngine(
                BatchedSparrowWorker(xtr, ytr, cfg),
                EngineConfig(n_workers=w, max_rounds=10, seed=0,
                             rounds_per_dispatch=rpd),
            )
            runs[rpd] = eng.run()
        assert runs[4].final_certificates == runs[1].final_certificates
        assert runs[4].history == runs[1].history
        assert runs[4].messages_sent == runs[1].messages_sent


# ---------------------------------------------------------------------------
# Batched Sparrow vs the unbatched oracle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_data():
    xb, y, _ = make_splice_like(SpliceConfig(n=20_000, d=16, num_bins=8, seed=3))
    return train_test_split(xb, y)


def _cfg(w, **kw):
    base = dict(
        sample_size=1024,
        capacity=64,
        scanner=ScannerConfig(chunk_size=512, num_bins=8, gamma0=0.25),
        n_workers=w,
    )
    base.update(kw)
    return SparrowConfig(**base)


class TestBatchedSparrow:
    def test_feature_masks_match_unbatched(self, small_data):
        xtr, ytr, _, _ = small_data
        cfg = _cfg(3, ownership_redundancy=2)
        uw = SparrowWorker(xtr, ytr, cfg)
        masks = feature_ownership_masks(uw.d, 3, 2)
        for i in range(3):
            np.testing.assert_array_equal(masks[i], np.asarray(uw.feature_mask(i)))

    def test_scan_segments_match_unbatched(self, small_data):
        """40 scan segments, 3 workers: certificates, models and sample
        margins must match the per-worker oracle."""
        xtr, ytr, _, _ = small_data
        w = 3
        cfg = _cfg(w, ess_threshold=0.0)  # no resample inside this window
        bw = BatchedSparrowWorker(xtr, ytr, cfg)
        uw = SparrowWorker(xtr, ytr, cfg)
        bstate = bw.init_batch(w, 0)
        ustates = [uw.init_state(i, 1000 * i) for i in range(w)]
        for i in range(w):
            np.testing.assert_array_equal(
                np.asarray(bstate.sample.xb[i]), np.asarray(ustates[i].sample.xb)
            )
        mask = jnp.ones((w,), bool)
        for _ in range(40):
            bstate, _, _ = bw.scan_round(bstate, mask)
            ustates = [uw.run_segment(s)[0] for s in ustates]
        np.testing.assert_allclose(
            np.asarray(bstate.cert),
            np.asarray([s.cert for s in ustates], np.float32),
            rtol=1e-5,
            atol=1e-6,
        )
        for i in range(w):
            assert int(bstate.model.count[i]) == int(ustates[i].model.count)
            np.testing.assert_array_equal(
                np.asarray(bstate.model.feat[i]), np.asarray(ustates[i].model.feat)
            )
            np.testing.assert_allclose(
                np.asarray(bstate.model.alpha[i]),
                np.asarray(ustates[i].model.alpha),
                rtol=1e-5,
            )
            np.testing.assert_allclose(
                np.asarray(bstate.sample.margin_l[i]),
                np.asarray(ustates[i].sample.margin_l),
                rtol=1e-4,
                atol=1e-5,
            )

    @pytest.mark.slow
    def test_resample_path_matches_unbatched(self, small_data):
        """Aggressive ESS threshold forces resamples; the batched redraw
        must be bit-identical (same RNG stream, same systematic sampler)."""
        xtr, ytr, _, _ = small_data
        w = 2
        cfg = _cfg(w, ess_threshold=0.9)
        bw = BatchedSparrowWorker(xtr, ytr, cfg)
        uw = SparrowWorker(xtr, ytr, cfg)
        bstate = bw.init_batch(w, 0)
        ustates = [uw.init_state(i, 1000 * i) for i in range(w)]
        mask = jnp.ones((w,), bool)
        for _ in range(150):
            need = bw.needs_resample(bstate)
            if bool(jnp.any(need)):
                bstate, _ = bw.resample_round(bstate, need)
                bstate, _, _ = bw.scan_round(bstate, mask & ~need)
            else:
                bstate, _, _ = bw.scan_round(bstate, mask)
            ustates = [uw.run_segment(s)[0] for s in ustates]
        assert int(bstate.resamples.sum()) >= 1
        np.testing.assert_array_equal(
            np.asarray(bstate.resamples), [s.resamples for s in ustates]
        )
        np.testing.assert_allclose(
            np.asarray(bstate.cert),
            np.asarray([s.cert for s in ustates], np.float32),
            rtol=1e-4,
            atol=1e-5,
        )
        for i in range(w):
            np.testing.assert_array_equal(
                np.asarray(bstate.sample.xb[i]), np.asarray(ustates[i].sample.xb)
            )

    def test_kernel_scan_path_under_vmap(self, small_data):
        """ScannerConfig.use_kernel routes the batched scan through the
        Pallas edge_scan kernel; histograms and certs must agree with
        the pure-jnp path."""
        xtr, ytr, _, _ = small_data
        states = {}
        for use_kernel in (True, False):
            cfg = _cfg(
                2,
                sample_size=256,
                capacity=16,
                scanner=ScannerConfig(
                    chunk_size=128, num_bins=8, gamma0=0.25, use_kernel=use_kernel
                ),
            )
            b = BatchedSparrowWorker(xtr, ytr, cfg)
            s = b.init_batch(2, 0)
            for _ in range(6):
                s, _, _ = b.scan_round(s, jnp.ones((2,), bool))
            states[use_kernel] = s
        np.testing.assert_allclose(
            np.asarray(states[True].scanner.hist),
            np.asarray(states[False].scanner.hist),
            rtol=1e-4,
            atol=1e-4,
        )
        np.testing.assert_allclose(
            np.asarray(states[True].cert), np.asarray(states[False].cert), rtol=1e-4
        )

    def test_disk_data_is_a_chunk_argument(self, small_data):
        """The engine passes the disk dataset into its jitted chunk as an
        argument: closed over, jit would embed it as a constant (JAX
        warns above the threshold; the disk set here is ~1.2 MB)."""
        xtr, ytr, _, _ = small_data
        worker = BatchedSparrowWorker(xtr, ytr, _cfg(2, sample_size=256, capacity=16))
        limit = jax.config.jax_captured_constants_warn_bytes
        jax.config.update("jax_captured_constants_warn_bytes", 200_000)
        try:
            with warnings.catch_warnings():
                warnings.filterwarnings("error", message=".*constants were captured.*")
                make_engine(
                    worker, EngineConfig(n_workers=2, max_rounds=4, fault_spec="")
                ).run()
        finally:
            jax.config.update("jax_captured_constants_warn_bytes", limit)

    def test_adopt_batch_matches_unbatched(self, small_data):
        """Adoption = prefix-sharing margin transfer; batched vs oracle."""
        xtr, ytr, _, _ = small_data
        w = 2
        cfg = _cfg(w, ess_threshold=0.0)
        bw = BatchedSparrowWorker(xtr, ytr, cfg)
        uw = SparrowWorker(xtr, ytr, cfg)
        bstate = bw.init_batch(w, 0)
        ustates = [uw.init_state(i, 1000 * i) for i in range(w)]
        mask = jnp.ones((w,), bool)
        for _ in range(30):  # let both workers grow different models
            bstate, _, _ = bw.scan_round(bstate, mask)
            ustates = [uw.run_segment(s)[0] for s in ustates]
        assert min(int(c) for c in bstate.model.count) > 0
        # worker 1 adopts worker 0's model in both substrates
        models = bw.export_models(bstate)
        donor = jax.tree_util.tree_map(lambda a: a[jnp.asarray([0, 0])], models)
        take = jnp.asarray([False, True])
        bstate2, cost = bw.adopt_batch(bstate, donor, bstate.cert[jnp.asarray([0, 0])], take)
        u1 = uw.adopt(ustates[1], ustates[0].model, ustates[0].cert)
        assert float(cost[0]) == 0.0 and float(cost[1]) > 0.0
        assert float(bstate2.cert[1]) == pytest.approx(ustates[0].cert, rel=1e-6)
        np.testing.assert_allclose(
            np.asarray(bstate2.sample.margin_l[1]),
            np.asarray(u1.sample.margin_l),
            rtol=1e-4,
            atol=1e-4,
        )
        # worker 0 untouched
        np.testing.assert_array_equal(
            np.asarray(bstate2.model.feat[0]), np.asarray(bstate.model.feat[0])
        )

    def test_common_prefix_len_matches_numpy(self, small_data):
        xtr, ytr, _, _ = small_data
        w = 2
        cfg = _cfg(w, ess_threshold=0.0)
        bw = BatchedSparrowWorker(xtr, ytr, cfg)
        bstate = bw.init_batch(w, 0)
        mask = jnp.ones((w,), bool)
        for _ in range(25):
            bstate, _, _ = bw.scan_round(bstate, mask)
        a = jax.tree_util.tree_map(lambda x: x[0], bstate.model)
        b = jax.tree_util.tree_map(lambda x: x[1], bstate.model)
        ref = SparrowWorker._common_prefix(a, b)
        assert int(common_prefix_len(a, b)) == ref
        assert int(common_prefix_len(a, a)) == int(a.count)


@pytest.mark.slow
class TestEngineSparrowEndToEnd:
    def test_engine_learns_and_gossips(self, small_data):
        xtr, ytr, xte, yte = small_data
        from repro.boosting.stumps import exp_loss

        w = 8
        cfg = _cfg(w, capacity=48, scanner=ScannerConfig(chunk_size=256, num_bins=8))
        worker = BatchedSparrowWorker(xtr, ytr, cfg)
        eng = TMSNEngine(
            worker, EngineConfig(n_workers=w, max_rounds=120, seed=0)
        )
        res = eng.run()
        certs = np.asarray(res.final_certificates)
        assert certs.min() < -0.05
        assert res.messages_sent > 0 and res.messages_accepted > 0
        # gossip keeps the cohort tight
        assert certs.max() - certs.min() < 0.05
        best = int(np.argmin(certs))
        assert float(exp_loss(res.final_models[best], xte, yte)) < 0.95
        # best-cert envelope is monotone by construction
        trace = res.best_certificate_trace()
        vals = [c for _, c in trace]
        assert vals == sorted(vals, reverse=True)

    def test_engine_heterogeneous_run(self, small_data):
        """Laggards + a fail-stop + real link delays in one engine run."""
        xtr, ytr, _, _ = small_data
        w = 8
        cfg = _cfg(w, capacity=48, scanner=ScannerConfig(chunk_size=256, num_bins=8))
        worker = BatchedSparrowWorker(xtr, ytr, cfg)
        speed = np.ones(w)
        speed[-1] = 0.1
        fail = np.full(w, 10**6)
        fail[-2] = 30
        delays = quantize_latency(0.05, 0.02, 0.05, w, seed=1)
        eng = TMSNEngine(
            worker,
            EngineConfig(
                n_workers=w,
                delay_rounds=delays,
                speed=speed,
                fail_round=fail,
                max_rounds=120,
                seed=0,
            ),
        )
        res = eng.run()
        live = [c for i, c in enumerate(res.final_certificates) if i != w - 2]
        assert min(live) < -0.03  # survivors progressed despite failure + laggard
