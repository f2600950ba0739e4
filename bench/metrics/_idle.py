"""The device's idle share of the traced window, averaged over chips."""


def share(ctx):
    red = ctx["trace"]
    if red.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
