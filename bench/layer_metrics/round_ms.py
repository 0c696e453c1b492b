"""Device milliseconds per round of the engine's jitted chunk program
(``lax.scan`` over ``_round_step``): the device time of its operations
in the traced window over the rounds the window's trainings ran."""

from _common import chunk_time, traced_rounds


def read(ctx):
    rounds = traced_rounds(ctx)
    t = chunk_time(ctx)
    if not rounds or not t:
        return None
    return 1e3 * t / rounds
