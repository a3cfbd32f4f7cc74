"""The benchmark's copy of the horizon generator starts faithful: at a
fixed seed it reproduces the program's ``EdgeSystem.horizon`` exactly."""
import numpy as np
import pytest

from bench.traffic import horizon

LEAVES = ("acc", "xi", "size", "eff", "budgets_b", "budgets_c")


@pytest.mark.parametrize("n_cameras,n_servers,n_slots,seed", [
    (30, 3, 16, 0),            # the paper's setting
    (300, 10, 12, 7),          # a suite-sized fleet
    (64, 4, 71, 2**31 + 5),    # a long horizon, a seed past 32 bits
])
def test_copy_reproduces_edge_system_horizon(n_cameras, n_servers,
                                             n_slots, seed):
    from repro.core import profiles
    bw = 30e6 * n_cameras / (10 * n_servers)
    flops = 50e12 * n_cameras / (10 * n_servers)
    want = profiles.EdgeSystem(
        n_cameras=n_cameras, n_servers=n_servers, n_slots=n_slots,
        mean_bandwidth_hz=bw, mean_compute_flops=flops,
        seed=seed).horizon(n_slots)
    got = horizon.build(n_cameras, n_servers, n_slots, bw, flops, seed)
    for leaf in LEAVES:
        np.testing.assert_array_equal(
            np.asarray(got[leaf], np.float32), np.asarray(getattr(want, leaf)),
            err_msg=leaf)


def test_permute_and_window_keep_every_value():
    tab = horizon.build(12, 2, 6, 60e6, 1e14, 3)
    perm = np.random.default_rng(0).permutation(12)
    moved = horizon.window(horizon.permute_cameras(tab, perm), 2, 5)
    np.testing.assert_array_equal(moved["acc"], tab["acc"][2:5, perm])
    np.testing.assert_array_equal(moved["eff"], tab["eff"][perm])
    np.testing.assert_array_equal(moved["budgets_c"], tab["budgets_c"][2:5])
    np.testing.assert_array_equal(moved["xi"], tab["xi"])
