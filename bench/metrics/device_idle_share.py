"""Per-layer metric `device_idle_share`: the share of the traced window,
in %, in which no operation ran on the device (averaged over the chips)."""


def read(ctx):
    share = ctx.idle_share()
    return None if share is None else 100.0 * share
