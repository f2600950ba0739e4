"""Device time in the ``global_step`` named scope, forward and backward."""
from bench import marks


def ms_per_iteration(ctx):
    red = marks.of(ctx)
    t = red and red.scope_s("global_step", "global_step_bwd")
    if not t or not ctx["iterations"]:
        return None
    return 1e3 * t / ctx["iterations"]
