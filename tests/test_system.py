"""End-to-end system behaviour (the paper's Fig. 16 testbed analog)."""
import numpy as np
import pytest

from repro.core import baselines, lbcd, profiles
from repro.serving import AnalyticsService


def _system(seed=0):
    return profiles.EdgeSystem(
        n_cameras=12, n_servers=2, n_slots=20, seed=seed,
        mean_bandwidth_hz=12e6, mean_compute_flops=12e12)


def test_e2e_lbcd_service_beats_baselines_on_measured_aopi():
    """Measured (data-plane) AoPI: LBCD < DOS and JCAB, accuracy >= floor."""
    ctrl = lbcd.LBCDController(_system(), v=10.0, p_min=0.65)
    svc = AnalyticsService(ctrl, mode="mm1", epoch_duration=2000.0)
    reps = svc.run(8)
    lbcd_measured = np.mean([r.measured_aopi for r in reps])
    accs = np.mean([r.accuracy for r in reps])

    results = {}
    for name in ("DOS", "JCAB"):
        bl = baselines.make(name, _system())
        bsvc = AnalyticsService(bl, mode="mm1", epoch_duration=2000.0)
        breps = bsvc.run(8)
        results[name] = np.mean([r.measured_aopi for r in breps])

    assert lbcd_measured < results["DOS"]
    assert lbcd_measured < results["JCAB"]
    assert accs >= 0.55          # converging toward P_min from below


def test_e2e_closed_form_guides_real_queues():
    """The slot decisions' predicted ordering holds in the measured data
    plane across epochs (theory is a usable control signal)."""
    ctrl = lbcd.LBCDController(_system(seed=3), v=10.0, p_min=0.6)
    svc = AnalyticsService(ctrl, mode="mm1", epoch_duration=2000.0)
    reps = svc.run(6)
    pred = np.array([r.predicted_aopi for r in reps])
    meas = np.array([r.measured_aopi for r in reps])
    # predictions within 30% of measurements on average
    assert np.mean(np.abs(pred - meas) / np.maximum(meas, 1e-9)) < 0.3


@pytest.mark.parametrize("seed", range(3))
def test_e2e_real_engine_service_runs(seed):
    """The replay engine driven by LBCD for one engine-mode epoch."""
    from repro.serving import make_replay_engine

    eng = make_replay_engine(4, max_len=96, decode_tokens=2, seed=seed)
    system = profiles.EdgeSystem(n_cameras=4, n_servers=1, n_slots=4,
                                 seed=seed)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6)
    svc = AnalyticsService(ctrl, mode="engine", engine=eng,
                           epoch_duration=2.0)
    rep = svc.run_epoch(0)
    assert np.isfinite(rep.measured_aopi)
    assert rep.measured_aopi >= 0.0
