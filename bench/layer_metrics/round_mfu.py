"""The whole round's share of the chip's peak: the least time the work a
round requires could take (``counts.round_work``: every worker's chunk
rows, bins at one byte, at peak HBM bandwidth or peak FLOP/s, whichever
is longer), over the measured device time per round."""

import counts
from _common import chunk_time, traced_rounds


def read(ctx):
    rounds = traced_rounds(ctx)
    t = chunk_time(ctx)
    if not rounds or not t:
        return None
    cfg = ctx["config"]
    rows = min(cfg["scanner"]["chunk_size"], cfg["sparrow"]["sample_size"])
    nbytes, ops = counts.round_work(cfg["engine"]["n_workers"], rows, cfg["data"]["d"])
    least, _ = counts.least_time(nbytes, ops, counts.peaks(ctx["device_kind"]))
    return 100.0 * least * rounds / t
