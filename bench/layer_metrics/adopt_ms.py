"""Device milliseconds per round of adoption: the chunk program's leaf
operations in the named scope ``tmsn.adopt`` (the payload lookup in the
snapshot ring and the adoption ``lax.cond``, whether a worker adopts or
not), over the rounds the window's trainings ran (``_program.scope_ms``)."""

from _program import scope_reader

read = scope_reader("tmsn.adopt")
