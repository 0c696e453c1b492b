"""Shared lookups of the per-layer readers: the window's rounds and the
device time of named programs and kernels on the busiest device."""

import re


def traced_rounds(ctx):
    """Rounds run by the trainings inside the traced window."""
    return sum(t["rounds"] for t in ctx["trainings"])


def chunk_time(ctx):
    """Device seconds of the engine's chunk program on the busiest device."""
    tr = ctx["trace"]
    if tr is None:
        return None
    dev = tr.busiest()
    return tr.module_time(r"chunk", dev) or None


def kernel_calls(ctx, pattern):
    """``(calls, seconds)`` of the kernel events matching ``pattern`` on
    the busiest device."""
    tr = ctx["trace"]
    if tr is None:
        return 0, 0.0
    dev = tr.busiest()
    rx = re.compile(pattern)
    calls = sum(c for n, c in dev.op_calls.items() if rx.search(n))
    return calls, sum(t for n, t in dev.op_s.items() if rx.search(n))


def idle_share(ctx):
    """100 x (1 - busy / window) on the busiest device; the window runs
    from the first harness span's start to the last span's end."""
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busiest().busy_s / tr.window_s)
