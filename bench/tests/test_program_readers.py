"""The readers of the program's own records (``repro.core.telemetry``) on a
hand-built context: spans and counters of the window's runs, and the
device time of the chunk program's named scopes."""

import sys

import pytest

import run
from trace_reduce import Device, Trace

from repro.core import telemetry
from repro.launch.hlo_analysis import ScopeEntry

SCOPE_READERS = ("scan_round_ms", "adopt_ms", "resample_ms", "round_unscoped_ms")
SPAN_READERS = ("init_ms", "finalize_ms", "chunk_host_ms")
ROUNDS = 104

# one program: a leaf per scope, an unscoped one and the scan's while
SCOPES = {
    "fusion.1": ScopeEntry("tmsn.scan", True),
    "edge_scan.2": ScopeEntry("tmsn.scan", True),
    "copy.250": ScopeEntry("tmsn.adopt", True),
    "copy.243": ScopeEntry("tmsn.resample", True),
    "round_step.3": ScopeEntry("tmsn.deliver", True),
    "fusion.4": ScopeEntry("tmsn.broadcast", True),
    "fusion.9": ScopeEntry("unscoped", True),
    "while.116": ScopeEntry("unscoped", False),
}
OPS = {  # device seconds in the window
    "%fusion.1": 0.5, "%edge_scan.2": 0.25, "%copy.250": 3.0, "%copy.243": 2.5,
    "%round_step.3": 0.01, "%fusion.4": 0.02, "%fusion.9": 0.1, "%while.116": 9.0,
    "%fusion.77": 4.0,  # an eager op's, no instruction of the chunk program
}
CHUNK_S = 9.5


def _record(run_id, rounds, chunks, t0):
    """A run: 200 ms of init, then per chunk 1 ms of dispatch and 2 ms
    of host work, and 50 ms of finalize."""
    ms = 1_000_000
    rec = telemetry.RunRecord(run_id)
    rec.counters.update(rounds=rounds, chunks=chunks)
    rec.spans.append(telemetry.Span(run_id, "tmsn.init", "tmsn.run", t0, t0 + 200 * ms))
    for k in range(chunks):
        at = t0 + (200 + 3 * k) * ms
        rec.spans.append(telemetry.Span(run_id, "tmsn.dispatch", "tmsn.run", at, at + ms))
        rec.spans.append(telemetry.Span(run_id, "tmsn.host", "tmsn.run", at + ms, at + 3 * ms))
    rec.spans.append(telemetry.Span(run_id, "tmsn.finalize", "tmsn.run", t0 + 3000 * ms, t0 + 3050 * ms))
    rec.spans.append(telemetry.Span(run_id, "tmsn.run", None, t0, t0 + 3050 * ms))
    return rec


@pytest.fixture
def ctx(monkeypatch):
    """Four trainings of 104 rounds; the ring also holds a warm-up."""
    records = [_record(k, ROUNDS, 13, k * 10**10) for k in range(5)]
    monkeypatch.setattr(telemetry, "runs", lambda last=None: records[-last:])
    monkeypatch.setattr(telemetry, "programs", lambda: [telemetry.Program("k", SCOPES)])
    dev = Device("/device:TPU:0", busy_s=12.0, op_s=dict(OPS), module_s={"jit_chunk(1)": CHUNK_S})
    return {
        "trainings": [{"rounds": ROUNDS} for _ in range(4)],
        "trace": Trace(window=(0.0, 13.0), devices=[dev], spans=[]),
        "config": {}, "chips": 1, "device_kind": "TPU v5 lite",
    }


def read(name, ctx):
    return run.load_reader("layer_metrics", name)(ctx)


def test_scopes_and_the_rest_add_up_to_round_ms(ctx):
    round_ms = read("round_ms", ctx)
    got = {n: read(n, ctx) for n in SCOPE_READERS}
    rounds = 4 * ROUNDS
    assert got["scan_round_ms"] == pytest.approx(1e3 * 0.75 / rounds)
    assert got["adopt_ms"] == pytest.approx(1e3 * 3.0 / rounds)
    assert got["resample_ms"] == pytest.approx(1e3 * 2.5 / rounds)
    other = 1e3 * (0.01 + 0.02) / rounds  # deliver, broadcast
    assert sum(got.values()) + other == pytest.approx(round_ms)


def test_span_readers(ctx):
    assert read("init_ms", ctx) == pytest.approx(200.0)
    assert read("finalize_ms", ctx) == pytest.approx(50.0)
    assert read("chunk_host_ms", ctx) == pytest.approx(3.0)  # 1 ms dispatch + 2 ms host a chunk


@pytest.mark.parametrize("name", SCOPE_READERS + SPAN_READERS)
def test_none_without_the_telemetry_module(ctx, monkeypatch, name):
    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", SCOPE_READERS + SPAN_READERS)
def test_none_when_the_records_do_not_match_the_window(ctx, monkeypatch, name):
    assert read(name, ctx) is not None
    ctx["trainings"] = ctx["trainings"] * 2  # more trainings than records
    assert read(name, ctx) is None
    ctx["trainings"] = [{"rounds": ROUNDS + 1}] * 4  # other rounds
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_collision_guard(ctx, name):
    # an eager op named like a chunk leaf lifts the leaves above the program
    ctx["trace"].devices[0].op_s["%fusion.9"] += 0.4 * CHUNK_S
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_none_when_programs_disagree(ctx, monkeypatch, name):
    other = dict(SCOPES, **{"copy.250": ScopeEntry("tmsn.scan", True)})
    progs = [telemetry.Program("k", SCOPES), telemetry.Program("j", other)]
    monkeypatch.setattr(telemetry, "programs", lambda: progs)
    assert read(name, ctx) is None
