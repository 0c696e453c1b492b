"""What the program records of itself (``repro.core.telemetry``), for the
readers of its spans and named scopes.

The window's trainings are the last ``len(ctx["trainings"])`` run
records: the warm-ups come earlier and the reference runs no engine.
Every lookup returns None where there is nothing to read: a program
without the module, fewer run records than the window's trainings, or
records whose rounds are not the trainings' rounds.
"""

from _common import chunk_time, traced_rounds


def _telemetry():
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    return telemetry


def window_runs(ctx):
    """The window's run records, oldest first, or None."""
    tel = _telemetry()
    trainings = ctx["trainings"]
    if tel is None or not trainings:
        return None
    recs = tel.runs(last=len(trainings))
    if [r.counters.get("rounds") for r in recs] != [t["rounds"] for t in trainings]:
        return None
    return recs


def span_ms(ctx, names, per_chunk=False):
    """Milliseconds of the spans ``names`` summed, per training of the
    window (or per chunk it dispatched)."""
    recs = window_runs(ctx)
    if recs is None:
        return None
    n = sum(r.counters.get("chunks", 0) for r in recs) if per_chunk else len(recs)
    if not n:
        return None
    return 1e-6 * sum(r.total_ns(name) for r in recs for name in names) / n


def scope_ms(ctx):
    """Device milliseconds per round of each named scope of the chunk
    program, and ``unscoped``: the chunk program's time per round less
    every scoped leaf's, so that the values add up to ``round_ms``.

    An operation of the trace counts where its name is a leaf
    instruction of a registered chunk program. The trace merges names
    over every program of the window, so an eager operation can share
    one: where the leaves' time exceeds the chunk program's by more
    than 1%, or two programs give one name different scopes, this
    returns None rather than an inflated number."""
    tel = _telemetry()
    tr = ctx["trace"]
    if tel is None or tr is None or window_runs(ctx) is None:
        return None
    rounds, chunk = traced_rounds(ctx), chunk_time(ctx)
    if not rounds or not chunk:
        return None
    owner = {}
    for prog in tel.programs():
        for name, entry in prog.scopes.items():
            if owner.setdefault(name, entry) != entry:
                return None
    per = dict.fromkeys(tel.SCOPES, 0.0)
    leaves = 0.0
    for op, t in tr.busiest().op_s.items():
        entry = owner.get(op.lstrip("%"))
        if entry is None or not entry.leaf:
            continue
        leaves += t
        if entry.scope in per:
            per[entry.scope] += t
    if leaves > 1.01 * chunk:
        return None
    out = {scope: 1e3 * t / rounds for scope, t in per.items()}
    out[tel.UNSCOPED] = 1e3 * (chunk - sum(per.values())) / rounds
    return out


def scope_reader(scope):
    """A reader of one entry of :func:`scope_ms`."""

    def read(ctx):
        ms = scope_ms(ctx)
        return None if ms is None else ms[scope]

    return read
