"""The float64 reference's own arithmetic: the AoPI partials, the
water-fill, and the solver numbers on an allocation it made itself."""
import numpy as np
import pytest

from bench import reference as R


@pytest.mark.parametrize("pol", [R.FCFS, R.LCFSP])
def test_aopi_partials_match_finite_differences(pol):
    rng = np.random.default_rng(0)
    lam = rng.uniform(1.0, 5.0, 64)
    mu = lam * rng.uniform(1.2, 4.0, 64)
    p = rng.uniform(0.3, 0.9, 64)
    d_lam, d_mu = R.aopi_partials(lam, mu, p, pol)
    h = 1e-6
    for x, d, bump in ((lam, d_lam, lambda s: (lam * s, mu)),
                       (mu, d_mu, lambda s: (lam, mu * s))):
        fd = (R.closed_form_aopi(*bump(1 + h), p, pol)
              - R.closed_form_aopi(*bump(1 - h), p, pol)) / (2 * h * x)
        np.testing.assert_allclose(d, fd, rtol=1e-7)


def test_waterfill_gives_lcfsp_bandwidth_its_closed_form():
    # LCFSP: A = (1 + 1/p) / (k u) + ..., so at one price every camera's
    # share goes as sqrt((1 + 1/p) / k), and the shares fill the budget.
    rng = np.random.default_rng(1)
    k, p = rng.uniform(0.5, 4.0, 12), rng.uniform(0.3, 0.9, 12)
    group = np.repeat([0, 1, 2], 4)
    x = R.waterfill(lambda u: -(1 + 1 / p) / (k * u * u),
                    np.full(12, 1e-12), np.ones(12), group, 3)
    w = np.sqrt((1 + 1 / p) / k)
    want = w / np.bincount(group, w)[group]
    np.testing.assert_allclose(x, want, rtol=1e-10)


def test_waterfill_keeps_a_slack_budget_at_the_caps_and_refuses_floors():
    # Interior minima under the budget: the price is 0, each at its own.
    x = R.waterfill(lambda u: u - 0.1, np.full(3, 1e-9), np.ones(3),
                    np.zeros(3, int), 1)
    np.testing.assert_allclose(x, 0.1, rtol=1e-9)
    # Floors over the budget have no solution.
    x = R.waterfill(lambda u: -1 / u, np.full(2, 0.6), np.ones(2),
                    np.zeros(2, int), 1)
    assert np.isnan(x).all()


def test_solver_numbers_read_rounding_on_the_references_own_allocation():
    rng = np.random.default_rng(2)
    n, n_m, n_r = 6, 2, 3
    tables = {"acc": rng.uniform(0.5, 0.95, (1, n, n_m, n_r)),
              "xi": rng.uniform(1e9, 5e9, (n_m, n_r)),
              "size": rng.uniform(1e5, 4e5, n_r),
              "eff": rng.uniform(3.0, 7.0, n),
              "budgets_b": np.array([[3e6, 3e6]]),
              "budgets_c": np.array([[5e12, 5e12]])}
    assign = np.array([[0, 0, 0, 1, 1, 1]])
    pol = np.full((1, n), R.LCFSP)
    r, m = np.zeros((1, n), int), np.zeros((1, n), int)
    k = tables["eff"] / tables["size"][0]
    inv_xi = 1.0 / tables["xi"][0, 0]
    p = tables["acc"][0, np.arange(n), 0, 0]
    group = assign.ravel()
    b = 3e6 * R.waterfill(lambda u: -(1 + 1 / p) / (k * 3e6 * u * u),
                          np.full(n, 1e-12), np.ones(n), group, 2)
    c = 5e12 * R.waterfill(lambda u: -1 / (p * inv_xi * 5e12 * u * u),
                           np.full(n, 1e-12), np.ones(n), group, 2)
    plan = {"r_idx": r, "m_idx": m, "pol": pol, "assign": assign,
            "b": b[None], "c": c[None]}
    gaps = R.solver_gaps(plan, tables, np.zeros(1), 10.0)
    assert gaps["budget_gap"] < 1e-12
    assert gaps["b_gap"] < 1e-9 and gaps["c_gap"] < 1e-9
    short = dict(plan, b=0.9 * plan["b"])
    assert R.solver_gaps(short, tables, np.zeros(1), 10.0)[
        "budget_gap"] == pytest.approx(0.1)
