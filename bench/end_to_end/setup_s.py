"""Set-up: process start to the end of the warm-up training (data made on
the device, worker and engine built, the cell's chunk program compiled
or loaded from the cache)."""


def read(ctx):
    return ctx["setup_s"]
