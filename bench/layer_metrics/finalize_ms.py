"""Host milliseconds per training of the engine's ``tmsn.finalize`` span:
from the end of the chunk loop to ``run()``'s return (the final fetches,
the traffic counters, the exported models and their per-worker slices)."""

from _program import span_ms


def read(ctx):
    return span_ms(ctx, ["tmsn.finalize"])
