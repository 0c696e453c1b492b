"""Device milliseconds per round of the resample ``lax.cond``: the chunk
program's leaf operations in the named scope ``tmsn.resample``, its cost
even in rounds in which no worker resamples, over the rounds the window's
trainings ran (``_program.scope_ms``)."""

from _program import scope_reader

read = scope_reader("tmsn.resample")
