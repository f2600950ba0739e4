"""The operation and byte counts against hand counts at toy sizes."""
from bench.work import iteration, psi2, reg_stats


def test_reg_stats_forward_by_hand():
    # n=2 rows, m=3, q=1, d=1: cross term 2*2*3*1 = 12, O(nm) terms
    # 4*6 = 24, exp 6, C 2*6*1 = 12, w K 6 and D 2*2*3*3 = 36, b 2*2 = 4.
    flops, nbytes = reg_stats.forward(2, 3, 1, 1)
    assert flops == 12 + 24 + 6 + 12 + 6 + 36 + 4 == 100
    # x, y, w per row (3 numbers x 2 rows), z 3, C 3, D 9, l 1 and sf2 2.
    assert nbytes == 4 * (6 + 3 + 3 + 9 + 1 + 2)


def test_reg_stats_backward_by_hand():
    # 2nmd + 2nm^2 + 2nm + 6nmq at n=2, m=3, q=1, d=1.
    assert reg_stats.backward(2, 3, 1, 1) == 12 + 36 + 12 + 36


def test_psi2_by_hand():
    # n=1, m=2, q=1: 4 pairs, each 4q + 3 = 7 forward and 8q + 3 = 11 back.
    flops, nbytes = psi2.forward(1, 2, 1)
    assert flops == 28 and psi2.backward(1, 2, 1) == 44
    assert nbytes == 4 * (3 + 2 + 4)


def test_iteration_counts_grow_with_rows():
    cfg = {"model": "sgpr", "m": 100, "q": 8, "d": 1}
    one, two = iteration.flops(cfg, 1000), iteration.flops(cfg, 2000)
    per_row = two - one
    fwd = reg_stats.forward(1000, 100, 8, 1)[0]
    bwd = reg_stats.backward(1000, 100, 8, 1)
    assert per_row == fwd + bwd + 3000
    assert iteration.global_step(3, 1, 1) == 3 * (18 + 54 + 18 + 18)
