"""Device milliseconds per round of the worker segment: the chunk
program's leaf operations in the named scope ``tmsn.scan`` (certificates,
``scan_round`` with its ``edge_scan`` kernel, certificates), over the
rounds the window's trainings ran (``_program.scope_ms``)."""

from _program import scope_reader

read = scope_reader("tmsn.scan")
