"""Share of the traced stretch in which no operation ran on the device: one
minus the union of device operation intervals over the stretch (%)."""


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
