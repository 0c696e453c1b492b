"""Host milliseconds per training of the engine's ``tmsn.init`` span:
from ``run()``'s start of the initial state (the eager per-worker draws)
until its certificates are on the host."""

from _program import span_ms


def read(ctx):
    return span_ms(ctx, ["tmsn.init"])
