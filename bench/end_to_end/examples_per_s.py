"""Examples processed per host-clock second: the examples of every
training in the window over their total time. Each worker family defines
its example and counts it in its ``summary`` (``bench/families/<family>.py``):
for Sparrow, a sample row scanned, W x min(chunk, sample) a round."""


def read(ctx):
    t = ctx["trainings"]
    return sum(x["examples"] for x in t) / sum(x["seconds"] for x in t)
