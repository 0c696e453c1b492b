"""Roofline share of the ``edge_scan`` kernel: the least time its work
requires (``counts.edge_scan_work`` over every worker's chunk, bins at
one byte) over the device time of its events, per call."""

import counts
from _common import kernel_calls


def read(ctx):
    calls, t = kernel_calls(ctx, r"edge_scan")
    if not calls or not t:
        return None
    cfg = ctx["config"]
    rows = min(cfg["scanner"]["chunk_size"], cfg["sparrow"]["sample_size"])
    nbytes, ops = counts.edge_scan_work(rows, cfg["data"]["d"], cfg["data"]["num_bins"])
    w_local = cfg["engine"]["n_workers"] // ctx["chips"]
    least, _ = counts.least_time(w_local * nbytes, w_local * ops, counts.peaks(ctx["device_kind"]))
    return 100.0 * least * calls / t
