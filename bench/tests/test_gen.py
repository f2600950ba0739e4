"""The benchmark's copied generators against the program's, row for row."""
import numpy as np

from bench.gen.flight import flight_rows
from bench.gen.usps import kmeans, pca, usps_images


def test_flight_rows_match_program_generator():
    from repro.data.synthetic import flight_like

    for seed in (0, 2**31 + 5):
        src = flight_like(n=6000, seed=seed)
        want = src.read(0, 6000)
        got = flight_rows(6000, seed)
        for k in ("mu", "y"):
            np.testing.assert_array_equal(got[k], want[k])
        win = src.read(4097, 5000)
        part = flight_rows(5000 - 4097, seed, start=4097)
        np.testing.assert_array_equal(part["mu"], win["mu"])


def test_usps_images_and_start_match_program():
    from repro.core.init_utils import kmeans as prog_kmeans
    from repro.core.init_utils import pca as prog_pca
    from repro.data.synthetic import usps_like

    want_y, want_l = usps_like(np.random.default_rng(7), n=300)
    got_y, got_l = usps_images(np.random.default_rng(7), n=300)
    np.testing.assert_array_equal(got_l, want_l)
    np.testing.assert_array_equal(got_y, want_y)
    mu = pca(got_y, 10)
    np.testing.assert_array_equal(mu, prog_pca(want_y, 10))
    np.testing.assert_array_equal(kmeans(mu, 20, iters=5, seed=3),
                                  prog_kmeans(mu, 20, iters=5, seed=3))
