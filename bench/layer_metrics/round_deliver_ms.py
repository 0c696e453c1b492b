"""Device milliseconds per round of delivery: the ``round_step`` and
``queue_ingest`` kernels' events over the rounds the window ran."""

from _common import kernel_calls, traced_rounds


def read(ctx):
    rounds = traced_rounds(ctx)
    _, t = kernel_calls(ctx, r"round_step|queue_ingest")
    if not rounds or not t:
        return None
    return 1e3 * t / rounds
