"""The engine's own spans, counters and named scopes (repro.core.telemetry)
and the scope map of its compiled chunk program
(repro.launch.hlo_analysis.scope_map)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.boosting import BatchedSparrowWorker, SparrowConfig
from repro.boosting.scanner import ScannerConfig
from repro.core import telemetry
from repro.core.engine import EngineConfig, make_engine
from repro.core.engine_sharded import sharded_engine_available
from repro.data.splice import SpliceConfig, make_splice_like, train_test_split
from repro.launch.hlo_analysis import parse_instruction, scope_map, split_computations

# a while body that hands a copy (no metadata, as XLA inserts them) to a
# named conditional; the branches carry no metadata of their own either
# (long instructions are wrapped here and joined below)
HLO = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0}, pred[])->f32[8]{0}}

%keep (a.1: (f32[8])) -> (f32[8]) {
  %a.1 = (f32[8]{0}) parameter(0)
  %g.1 = f32[8]{0} get-tuple-element(%a.1), index=0
  %copy.3 = f32[8]{0} copy(%g.1)
  ROOT %t.1 = (f32[8]{0}) tuple(%copy.3)
}

%double (a.2: (f32[8])) -> (f32[8]) {
  %a.2 = (f32[8]{0}) parameter(0)
  %g.2 = f32[8]{0} get-tuple-element(%a.2), index=0
  %fusion.4 = f32[8]{0} fusion(%g.2), kind=kLoop, calls=%fused_double
  ROOT %t.2 = (f32[8]{0}) tuple(%fusion.4)
}

%fused_double (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  ROOT %add.0 = f32[8]{0} add(%p.0, %p.0)
}

%body (b.0: (f32[8], pred[])) -> (f32[8], pred[]) {
  %b.0 = (f32[8]{0}, pred[]) parameter(0)
  %g.3 = f32[8]{0} get-tuple-element(%b.0), index=0
  %g.4 = pred[] get-tuple-element(%b.0), index=1
  %copy.7 = f32[8]{0} copy(%g.3)
  %tuple.5 = (f32[8]{0}) tuple(%copy.7)
  %conditional.6 = (f32[8]) conditional(%g.4, %tuple.5, %tuple.5), branch_computations={%keep, %double},
    metadata={op_name="jit(f)/while/body/tmsn.adopt/cond"}
  %g.5 = f32[8]{0} get-tuple-element(%conditional.6), index=0
  %fusion.8 = f32[8]{0} fusion(%g.5), kind=kLoop, calls=%fused_double,
    metadata={op_name="jit(f)/while/body/tmsn.scan/vmap(tmsn.inner)/add"}
  ROOT %tuple.9 = (f32[8]{0}, pred[]) tuple(%fusion.8, %g.4)
}

%cond (c.0: (f32[8], pred[])) -> pred[] {
  %c.0 = (f32[8]{0}, pred[]) parameter(0)
  ROOT %g.6 = pred[] get-tuple-element(%c.0), index=1
}

ENTRY %main (x.0: f32[8], p.1: pred[]) -> f32[8] {
  %x.0 = f32[8]{0} parameter(0)
  %p.1 = pred[] parameter(1)
  %copy.10 = f32[8]{0} copy(%x.0)
  %tuple.11 = (f32[8]{0}, pred[]) tuple(%copy.10, %p.1)
  %while.12 = (f32[8]{0}, pred[]) while(%tuple.11), condition=%cond, body=%body,
    metadata={op_name="jit(f)/while"}
  ROOT %g.7 = f32[8]{0} get-tuple-element(%while.12), index=0
}
""".replace(",\n    metadata", ", metadata")


class TestScopeMap:
    def test_parser_reads_tuple_typed_computations(self):
        comps, entry = split_computations(HLO)
        assert entry == "main"
        assert set(comps) == {"keep", "double", "fused_double", "body", "cond", "main"}
        ins = parse_instruction(comps["body"][5])
        assert (ins.name, ins.opcode) == ("conditional.6", "conditional")
        assert ins.operands == ("g.4", "tuple.5", "tuple.5")
        assert ins.called == (("branch", "keep"), ("branch", "double"))

    def test_rules_in_order(self):
        m = scope_map(HLO)
        # rule 1: the innermost tmsn.* component of the instruction's own op_name
        assert m["conditional.6"].scope == "tmsn.adopt"
        assert m["fusion.8"].scope == "tmsn.inner"
        # rule 2: a copy without metadata takes its consumer's scope
        assert m["copy.7"].scope == "tmsn.adopt"
        # rule 3: branch instructions take the conditional's scope, fused
        # ones the fusion's
        assert m["copy.3"].scope == m["fusion.4"].scope == "tmsn.adopt"
        assert m["add.0"].scope in ("tmsn.adopt", "tmsn.inner")
        # nothing names the loop itself
        assert m["while.12"].scope == m["copy.10"].scope == "unscoped"

    def test_leaves(self):
        m = scope_map(HLO)
        assert not m["while.12"].leaf and not m["conditional.6"].leaf
        assert m["copy.7"].leaf and m["copy.3"].leaf and m["fusion.8"].leaf
        assert not m["add.0"].leaf  # inside a fusion: no device op of its own

    def test_compiled_scan_with_a_named_cond(self):
        def f(x, p):
            def body(c, _):
                with jax.named_scope("tmsn.adopt"):
                    y = jax.lax.cond(p, lambda v: v.at[0].set(1.0), lambda v: v, c)
                with jax.named_scope("tmsn.scan"):
                    return c * 2 + y, None

            return jax.lax.scan(body, x, None, length=3)[0]

        text = jax.jit(f).lower(jnp.ones((64,)), True).compile().as_text()
        m = scope_map(text)
        comps, _ = split_computations(text)
        ops = {i.name: i for c in comps.values() for i in map(parse_instruction, c) if i}
        conds = [n for n, i in ops.items() if i.opcode == "conditional"]
        whiles = [n for n, i in ops.items() if i.opcode == "while"]
        assert conds and whiles
        assert all(not m[n].leaf for n in conds + whiles)
        assert all(m[n].scope == "tmsn.adopt" for n in conds)
        branches = {b for n in conds for _, b in ops[n].called}
        in_branches = [i.name for b in branches for i in map(parse_instruction, comps[b]) if i]
        assert in_branches and all(m[n].scope == "tmsn.adopt" for n in in_branches)
        feeding = [o for n in conds for o in ops[n].operands if ops[o].opcode == "copy"]
        assert all(m[n].scope == "tmsn.adopt" for n in feeding)


# ---------------------------------------------------------------------------
# the engine's spans and counters, on a tiny Sparrow cohort
# ---------------------------------------------------------------------------

W = 4


@pytest.fixture(scope="module")
def data():
    xb, y, _ = make_splice_like(SpliceConfig(n=8_000, d=16, num_bins=8, seed=3))
    return train_test_split(xb, y)


def _engine(data, mesh=None, **kw):
    xtr, ytr, _, _ = data
    cfg = SparrowConfig(
        sample_size=256,
        capacity=16,
        scanner=ScannerConfig(chunk_size=128, num_bins=8, gamma0=0.25),
        n_workers=W,
    )
    econf = dict(n_workers=W, max_rounds=8, seed=0, rounds_per_dispatch=4, fault_spec="",
                 inflight_capacity=8, record_history=False, mesh=mesh)
    econf.update(kw)
    return make_engine(BatchedSparrowWorker(xtr, ytr, cfg), EngineConfig(**econf))


def _run(engine):
    engine.run()
    return telemetry.runs(last=1)[0]


@pytest.fixture(scope="module")
def fixed_rounds(data):
    """A fixed-round engine (no history, no target) and its first run."""
    eng = _engine(data)
    return eng, _run(eng), telemetry.programs()[-1]


class TestEngineTelemetry:
    def test_spans_and_counters_of_a_run(self, fixed_rounds):
        _, rec, _ = fixed_rounds
        names = [s.name for s in rec.spans]
        for n in (telemetry.RUN, telemetry.INIT, telemetry.DISPATCH, telemetry.HOST, telemetry.FINALIZE):
            assert n in names
        assert names.count(telemetry.DISPATCH) == 2 and names[-1] == telemetry.RUN
        parents = {s.name: s.parent for s in rec.spans}
        assert parents[telemetry.RUN] is None
        assert parents[telemetry.INIT] == parents[telemetry.FINALIZE] == telemetry.RUN
        assert parents[telemetry.DISPATCH] == telemetry.RUN
        assert rec.counters["chunks"] == 2 and rec.counters["rounds"] == 8
        assert rec.counters["chunk_compiles"] == 1
        assert all(s.run_id == rec.run_id and s.start_ns <= s.end_ns for s in rec.spans)

    def test_chunk_program_maps_every_leaf_to_a_scope(self, fixed_rounds):
        _, _, prog = fixed_rounds
        leaves = {e.scope for e in prog.scopes.values() if e.leaf}
        assert leaves <= set(telemetry.SCOPES) | {telemetry.UNSCOPED}
        for s in (telemetry.DELIVER, telemetry.ADOPT, telemetry.RESAMPLE, telemetry.SCAN,
                  telemetry.BROADCAST):
            assert s in leaves

    def test_second_run_compiles_nothing(self, fixed_rounds):
        eng, first, prog = fixed_rounds
        rec = _run(eng)
        assert rec.run_id > first.run_id
        assert rec.counters["chunk_compiles"] == 0
        assert telemetry.programs()[-1] is prog

    def test_snapshot_holds_the_kept_runs_as_plain_data(self, fixed_rounds):
        _, rec, prog = fixed_rounds
        snap = telemetry.snapshot()
        run = next(r for r in snap["runs"] if r["run_id"] == rec.run_id)
        assert run["counters"] == dict(rec.counters)
        assert [s["name"] for s in run["spans"]] == [s.name for s in rec.spans]
        assert {"key": prog.key, "instructions": len(prog.scopes)} in snap["programs"]

    def test_history_free_run_fetches_nothing_in_its_loop(self, data):
        short, long_ = _engine(data, max_rounds=4), _engine(data, max_rounds=16)
        a, b = _run(short), _run(long_)
        assert (a.counters["chunks"], b.counters["chunks"]) == (1, 4)
        # every fetch is in init or finalize: four times the chunks, the same fetches
        assert a.counters["host_fetches"] == b.counters["host_fetches"] > 0
        assert telemetry.FETCH not in {s.name for s in b.spans}

    def test_to_target_run_fetches_each_chunk(self, data):
        eng = _engine(data, max_rounds=64, record_history=True, target_certificate=-0.05)
        rec = _run(eng)
        fetches = [s for s in rec.spans if s.name == telemetry.FETCH]
        assert len(fetches) == rec.counters["chunks"] >= 1
        prog = telemetry.programs()[-1]
        assert telemetry.FREEZE in {e.scope for e in prog.scopes.values()}

    def test_trace_holds_the_spans_on_the_recorded_clock(self, fixed_rounds, tmp_path):
        from jax.profiler import ProfileData

        eng, _, _ = fixed_rounds
        with jax.profiler.trace(str(tmp_path)):
            rec = _run(eng)
        path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
        prof = ProfileData.from_file(path)
        start = next(int(v) for p in prof.planes for k, v in p.stats if k == "profile_start_time")
        events = [
            (ev.name, start + int(ev.start_ns), start + int(ev.start_ns + ev.duration_ns))
            for p in prof.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events if ev.name.startswith("tmsn.")
        ]
        for s in rec.spans:
            near = [e for e in events if e[0] == s.name and abs(e[1] - s.start_ns) < 1_000_000]
            assert near, f"{s.name} at {s.start_ns} is not in the trace"
            if s.parent is not None:
                _, lo, hi = near[0]
                assert any(n == s.parent and a <= lo and hi <= b for n, a, b in events)


    def test_adoptions_and_resamples_are_counted(self, data):
        """``adoptions`` is the result's accepted count and ``resamples``
        what the worker state counts, read once the run has ended (an ESS
        threshold of 1 resamples after every fire)."""
        xtr, ytr, _, _ = data
        cfg = SparrowConfig(
            sample_size=256,
            capacity=16,
            scanner=ScannerConfig(chunk_size=128, num_bins=8, gamma0=0.25),
            n_workers=W,
            ess_threshold=1.0,
        )
        eng = make_engine(
            BatchedSparrowWorker(xtr, ytr, cfg),
            EngineConfig(n_workers=W, max_rounds=16, seed=0, rounds_per_dispatch=4,
                         fault_spec="", inflight_capacity=8, record_history=False),
        )
        res = eng.run()
        rec = telemetry.runs(last=1)[0]
        state = eng._init_state()
        for _ in range(4):
            state, _ = eng._chunk_fn(4, state)(state)
        assert rec.counters["adoptions"] == res.messages_accepted > 0
        assert rec.counters["resamples"] == int(np.sum(state.worker.resamples)) > 0


@pytest.mark.skipif(not sharded_engine_available(4), reason="needs 4 devices")
def test_sharded_chunk_program_scopes_its_gossip(data):
    from repro.launch.mesh import make_worker_mesh

    rec = _run(_engine(data, mesh=make_worker_mesh(4)))
    assert rec.counters["rounds"] == 8
    scopes = {e.scope for e in telemetry.programs()[-1].scopes.values() if e.leaf}
    assert {telemetry.GOSSIP, telemetry.SCAN, telemetry.ADOPT} <= scopes
