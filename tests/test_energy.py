"""Energy-aware LBCD (the paper's §VII future-work item)."""
import numpy as np
import pytest

from repro.core import profiles
from repro.core.energy import EnergyAwareLBCD, EnergyModel
from repro.core.lbcd import LBCDController


def _system(seed=0):
    return profiles.EdgeSystem(n_cameras=12, n_servers=2, n_slots=40,
                               seed=seed, mean_bandwidth_hz=15e6,
                               mean_compute_flops=15e12)


def _rollout(ea, n_slots, per_slot):
    """``per_slot`` drives ``step`` (the path the serving and failover
    planes take) slot by slot; otherwise the scan engine runs all slots."""
    if per_slot:
        return [ea.step(t) for t in range(n_slots)]
    return ea.run(n_slots).records


# Power budgets (W a camera) far below what plain LBCD draws (about 5 W)
# that the controller can still meet at p_min = 0.6. At 0.2 W and below the
# two constraints cannot both hold: power settles near 0.2-0.25 W and the
# energy queue grows without bound. The first input runs the per-slot path
# over all 60 slots; the others run the scan engine.
@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("e_max", (0.25, 0.3, 0.4, 0.5))
def test_energy_queue_drives_power_toward_budget(e_max, seed):
    em = EnergyModel(e_max=e_max)
    ea = EnergyAwareLBCD(_system(seed), energy=em, v=10.0, p_min=0.6)
    recs = _rollout(ea, 60, per_slot=(e_max, seed) == (0.25, 0))
    pws = np.array([r.power for r in recs])

    # Plain LBCD power under the same model (no energy awareness).
    base = LBCDController(_system(seed), v=10.0, p_min=0.6).run(20)
    base_p = np.mean([em.power(r.decision.b, r.decision.c).mean()
                      for r in base.records])

    assert pws[20:].mean() < base_p / 5          # large power reduction
    # Monotone convergence toward the cap (Lyapunov asymptotics).
    w = [pws[i:i + 20].mean() for i in (0, 20, 40)]
    assert w[0] > w[1] > w[2]
    assert w[2] < em.e_max * 2.0
    # Price rises while above budget (queue doing its job).
    assert recs[-1].z > recs[10].z


def test_energy_queue_idle_when_budget_loose():
    em = EnergyModel(e_max=100.0)                # effectively unconstrained
    ea = EnergyAwareLBCD(_system(), energy=em, v=10.0, p_min=0.6)
    recs = [ea.step(t) for t in range(5)]
    assert recs[-1].z == 0.0
    # and behaves like plain LBCD (same decisions at scale 1.0)
    base = LBCDController(_system(), v=10.0, p_min=0.6)
    rb = [base.step(t) for t in range(5)]
    np.testing.assert_allclose(recs[0].aopi, rb[0].aopi, rtol=1e-5)


# p_min 0.55 runs the per-slot path; the other two the scan engine.
@pytest.mark.parametrize("p_min", (0.45, 0.5, 0.55))
def test_energy_accuracy_still_tracked(p_min):
    em = EnergyModel(e_max=0.3)
    ea = EnergyAwareLBCD(_system(), energy=em, v=5.0, p_min=p_min)
    recs = _rollout(ea, 40, per_slot=p_min == 0.55)
    accs = np.array([r.mean_acc for r in recs])
    assert accs[20:].mean() >= p_min - 0.05      # accuracy floor respected
