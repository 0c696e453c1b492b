"""Share of the traced window in which the busiest device ran no
operation: 100 x (1 - busy / window)."""

from _common import idle_share


def read(ctx):
    return idle_share(ctx)
