"""Mean host-clock seconds of a whole training to the target certificate,
over every training in the window (each from a fresh engine state, its
init included)."""


def read(ctx):
    if ctx["traffic"]["kind"] != "to_target":
        return None
    t = ctx["trainings"]
    return sum(x["seconds"] for x in t) / len(t)
