"""Mean device time of one run of the compiled decode step: the executable
with the most device time in the traced window (the step's XLA module)."""


def read(ctx):
    mods = ctx["trace"]["modules"]
    if not mods:
        return None
    name, v = max(mods.items(), key=lambda kv: kv[1]["s"])
    if not v["n"]:
        return None
    return 1e3 * v["s"] / v["n"]
