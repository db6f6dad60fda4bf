"""Share of the traced window in which no XLA module ran on the device, in
the closed-loop bulk cell."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
