"""Examples scanned per host-clock second: W x min(chunk, sample) x rounds,
summed over every training in the window, over their total time."""


def read(ctx):
    t = ctx["trainings"]
    cfg = ctx["config"]
    rows = min(cfg["scanner"]["chunk_size"], cfg["sparrow"]["sample_size"])
    examples = cfg["engine"]["n_workers"] * rows * sum(x["rounds"] for x in t)
    return examples / sum(x["seconds"] for x in t)
