"""Device milliseconds per round of the chunk program outside every named
scope of the round step: ``round_ms``'s time less the scoped leaf
operations', so that the scopes' per-round times and this one add up to
``round_ms`` (``_program.scope_ms``)."""

from _program import scope_reader

read = scope_reader("unscoped")
