"""Device idle share of the measured window in a served cell: 1 minus the
union of the device's program intervals over the window, from the
trace (one chip)."""


def read(ctx):
    red = ctx["reduced"]
    if not red.devices or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busiest().busy_s / red.window_s)
