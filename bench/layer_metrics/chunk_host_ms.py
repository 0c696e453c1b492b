"""Host milliseconds per chunk of the engine's ``tmsn.dispatch`` and
``tmsn.host`` spans: enqueueing a chunk, then the target check, history
and publishing that follow its fetch, the host work between two chunks."""

from _program import span_ms


def read(ctx):
    return span_ms(ctx, ["tmsn.dispatch", "tmsn.host"], per_chunk=True)
