"""Model operations of the traced iterations (map forward and backward,
global step and Adam, from shapes in ``bench/work/iteration.py``;
recomputation not counted), over the traced window and the chips' peak."""
from bench.work import iteration


def share(ctx):
    red = ctx["trace"]
    if red.window_s <= 0.0 or not ctx["iterations"]:
        return None
    ops = iteration.flops(ctx["config"], ctx["rows_per_iteration"])
    return 100.0 * ops * ctx["iterations"] / red.window_s / (
        ctx["chips"] * ctx["peak"]["flops_per_s"])
