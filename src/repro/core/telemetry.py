"""Spans, counters and named scopes of the TMSN engine, kept in memory.

Every :meth:`~repro.core.engine.TMSNEngine.run` opens a run record
(:func:`run_scope`). Inside it, :func:`span` times a host-side phase and
:func:`count` adds to a counter of that run; the last :data:`RING` run
records stay readable through :func:`runs` and :func:`snapshot`. A span
also opens a ``jax.profiler.TraceAnnotation`` under its name, so with
the profiler on it lands in the ``.xplane.pb`` beside the device events,
and its in-memory times are read from the same clock as the trace's host
events (``time.time_ns``): add the trace's ``profile_start_time`` to an
event's start to compare the two.

Inside the jitted round step, ``jax.named_scope`` marks the phases of a
round with the scope names below. They change only the compiled
program's metadata. :func:`register_program` keeps, for each distinct
compiled chunk program, the map from HLO instruction name to scope
(:func:`repro.launch.hlo_analysis.scope_map`), so that the device time a
profiler trace gives per instruction can be charged to a phase.

Recording is always on: with the profiler off a span costs a
TraceAnnotation, two clock reads and one list append.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

from repro.launch.hlo_analysis import UNSCOPED, ScopeEntry, scope_map

# --- host spans of one run() ------------------------------------------------
RUN = "tmsn.run"  # the root: one per run()
INIT = "tmsn.init"  # the initial state, until its certificates are on the host
DISPATCH = "tmsn.dispatch"  # enqueueing one chunk
FETCH = "tmsn.fetch"  # the blocking reads of one chunk's per-round info
HOST = "tmsn.host"  # target check, history and publishing for one chunk
FINALIZE = "tmsn.finalize"  # from the loop's end to the returned result

# --- named scopes of the round step ----------------------------------------
DELIVER = "tmsn.deliver"  # arrivals due this round, accept gate, credit
ADOPT = "tmsn.adopt"  # the payload lookup and the adoption cond
RESAMPLE = "tmsn.resample"  # the resample segment of the workers that need one
SCAN = "tmsn.scan"  # certificates, the worker segment, certificates
BROADCAST = "tmsn.broadcast"  # pushes into the in-flight state, ring write
GOSSIP = "tmsn.gossip"  # the sharded engine's all_gathers
FREEZE = "tmsn.freeze"  # the rows of a to-target chunk after its crossing round
SCOPES = (DELIVER, ADOPT, RESAMPLE, SCAN, BROADCAST, GOSSIP, FREEZE)

#: run records kept, newest last
RING = 64


class Span(NamedTuple):
    run_id: int
    name: str
    parent: str | None
    start_ns: int
    end_ns: int


@dataclasses.dataclass
class RunRecord:
    run_id: int
    spans: list[Span] = dataclasses.field(default_factory=list)
    counters: collections.Counter = dataclasses.field(default_factory=collections.Counter)
    _open: list[str] = dataclasses.field(default_factory=list, repr=False)

    def total_ns(self, name: str) -> int:
        """Summed length of this run's spans called ``name``."""
        return sum(s.end_ns - s.start_ns for s in self.spans if s.name == name)


class Program(NamedTuple):
    key: str  # digest of the compiled HLO text
    scopes: dict[str, ScopeEntry]


_ids = itertools.count(1)
_ring: collections.deque[RunRecord] = collections.deque(maxlen=RING)
_programs: collections.OrderedDict[str, Program] = collections.OrderedDict()
_lock = threading.Lock()
_local = threading.local()


def _stack() -> list[RunRecord]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _current() -> RunRecord | None:
    """The innermost open run record of this thread."""
    st = _stack()
    return st[-1] if st else None


class span:
    """Time a host-side phase of the current run under ``name``."""

    __slots__ = ("name", "_ann", "_rec", "_parent", "_t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        rec = self._rec = _current()
        self._ann.__enter__()
        self._t0 = time.time_ns()
        if rec is not None:
            self._parent = rec._open[-1] if rec._open else None
            rec._open.append(self.name)
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self._ann.__exit__(*exc)
        rec = self._rec
        if rec is not None:
            rec._open.pop()
            rec.spans.append(Span(rec.run_id, self.name, self._parent, self._t0, t1))
        return False


class run_scope:
    """Open a new run record and its root span :data:`RUN`; the record
    joins the ring when the scope closes."""

    __slots__ = ("record", "_span")

    def __init__(self):
        self.record = RunRecord(next(_ids))
        self._span = span(RUN, run_id=self.record.run_id)

    def __enter__(self) -> RunRecord:
        _stack().append(self.record)
        self._span.__enter__()
        return self.record

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        _stack().pop()
        with _lock:
            _ring.append(self.record)
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the current run (none: no-op)."""
    rec = _current()
    if rec is not None:
        rec.counters[name] += n


def runs(last: int | None = None) -> list[RunRecord]:
    """The newest ``last`` closed run records (all kept, by default),
    oldest first."""
    with _lock:
        recs = list(_ring)
    return recs if last is None else recs[len(recs) - min(last, len(recs)):]


def snapshot() -> dict:
    """Every kept run record and registered program, as plain data."""
    return {
        "runs": [
            {"run_id": r.run_id, "counters": dict(r.counters),
             "spans": [s._asdict() for s in r.spans]}
            for r in runs()
        ],
        "programs": [{"key": p.key, "instructions": len(p.scopes)} for p in programs()],
    }


def register_program(hlo_text: str) -> Program:
    """Keep the scope map of a compiled program: one entry per distinct
    HLO text, at most :data:`RING`, the one registered last at the end."""
    key = hashlib.sha256(hlo_text.encode()).hexdigest()[:16]
    with _lock:
        prog = _programs.get(key)
        if prog is not None:
            _programs.move_to_end(key)
            return prog
    prog = Program(key, scope_map(hlo_text))
    with _lock:
        _programs[key] = prog
        while len(_programs) > RING:
            _programs.popitem(last=False)
    return prog


def programs() -> list[Program]:
    """Registered programs, oldest first."""
    with _lock:
        return list(_programs.values())

