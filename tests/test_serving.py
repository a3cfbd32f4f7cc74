"""Serving: scheduler semantics, AoPI tracker, engine, LBCD-driven service."""
import math

import jax
import numpy as np
import pytest

from repro.core import lbcd, profiles, queues
from repro.faults import failover_assignment
from repro.serving import (AnalyticsService, AoPITracker, Frame, StreamQueue,
                           make_replay_engine)
from repro.serving.scheduler import FCFS, LCFSP


def test_fcfs_queue_order():
    q = StreamQueue(0, FCFS)
    for i in range(3):
        assert not q.on_arrival(Frame(0, i * 1.0, i * 1.0 + 0.1, seq=i))
    assert [q.pop().seq for _ in range(3)] == [0, 1, 2]


def test_lcfsp_preempts_and_keeps_only_latest():
    q = StreamQueue(0, LCFSP)
    q.on_arrival(Frame(0, 0.0, 0.1, seq=0))
    preempt = q.on_arrival(Frame(0, 1.0, 1.1, seq=1))
    assert preempt
    assert len(q) == 1 and q.pop().seq == 1


def test_aopi_tracker_matches_offline_integration():
    """Online tracker == queues._integrate_age on an in-order trace
    (completions preserve generation order, as in FCFS/LCFSP queues —
    the offline integrator's domain)."""
    rng = np.random.default_rng(0)
    n_ev = 200
    gen = np.sort(rng.uniform(0, 100, n_ev))
    done = np.maximum.accumulate(gen + rng.uniform(0.1, 2.0, n_ev)) \
        + np.linspace(0, 1e-3, n_ev)
    acc = rng.random(n_ev) < 0.7
    horizon = float(done[-1] + 1.0)
    expect = queues._integrate_age(gen, done, acc, horizon)
    tr = AoPITracker(1)
    for g, d, a in zip(gen, done, acc):
        tr.on_result(0, g, bool(a), float(d))
    assert tr.mean_aopi(0, horizon) == pytest.approx(expect, rel=1e-9)


@pytest.mark.parametrize("seed", range(5))
def test_replay_engine_params_are_the_explicit_draws(seed):
    """The replay engine's parameters are two normal draws from the halves
    of PRNGKey(seed): emb [32, 8] at scale 1, out [8, 32] at 1/sqrt(8)."""
    eng = make_replay_engine(3, seed=seed)
    k_emb, k_out = jax.random.split(jax.random.PRNGKey(seed), 2)
    emb = jax.random.normal(k_emb, (32, 8), np.float32)
    out = jax.random.normal(k_out, (8, 32), np.float32) * (1.0 / math.sqrt(8))
    assert sorted(eng.params) == ["emb", "out"]
    np.testing.assert_array_equal(np.asarray(eng.params["emb"]), emb)
    np.testing.assert_array_equal(np.asarray(eng.params["out"]), out)
    assert eng.params["emb"].dtype == eng.params["out"].dtype == np.float32
    np.testing.assert_array_equal(np.asarray(eng.cache["len"]),
                                  np.zeros(3, np.int32))
    np.testing.assert_array_equal(np.asarray(eng.cache["state"]),
                                  np.zeros((1, 3, 8), np.float32))


# Lane counts x decode lengths the engine tests run over.
ENGINE_SHAPES = pytest.mark.parametrize(
    "n_lanes,decode_tokens", [(n, d) for n in (2, 4) for d in (2, 8)])


@ENGINE_SHAPES
def test_engine_admit_decode_complete(n_lanes, decode_tokens):
    eng = make_replay_engine(n_lanes, decode_tokens=decode_tokens)
    f = Frame(3, 0.0, 0.0)
    assert eng.admit(f, np.arange(2, 10, dtype=np.int32))
    assert eng.utilization == 1.0 / n_lanes
    done = []
    for _ in range(decode_tokens + 3):
        done += eng.decode_tick()
        if done:
            break
    assert done and done[0].stream_id == 3
    assert len(done[0].tokens) == 1 + decode_tokens   # prefill token + decode
    assert eng.utilization == 0.0
    assert len(eng.free_lanes()) == n_lanes


@ENGINE_SHAPES
def test_engine_preemption_frees_lane(n_lanes, decode_tokens):
    eng = make_replay_engine(n_lanes, decode_tokens=decode_tokens)
    for i in range(n_lanes):
        assert eng.admit(Frame(i + 1, 0.0, 0.0),
                         np.arange(2, 8, dtype=np.int32))
    assert not eng.free_lanes()
    assert not eng.admit(Frame(99, 0.0, 0.0), np.arange(2, 8, dtype=np.int32))
    assert eng.preempt_stream(1) == 1
    assert len(eng.free_lanes()) == 1
    assert eng.lanes[0].stream_id == -1 and eng.lanes[0].remaining == 0
    assert eng.decode_tick() == []          # the other streams still run


@ENGINE_SHAPES
def test_engine_batched_decode_matches_sequential(n_lanes, decode_tokens):
    """Lanes decoding together produce the same tokens as each alone."""
    prompts = [np.arange(2 + 3 * i, 12 + 2 * i, dtype=np.int32)
               for i in range(n_lanes)]

    def run(eng, idx):
        for i in idx:
            assert eng.admit(Frame(i, 0, 0), prompts[i])
        outs = {}
        for _ in range(decode_tokens + 2):
            for r in eng.decode_tick():
                outs[r.stream_id] = r.tokens
        return outs

    together = run(make_replay_engine(n_lanes, decode_tokens=decode_tokens),
                   range(n_lanes))
    assert sorted(together) == list(range(n_lanes))
    for i in range(n_lanes):
        solo = run(make_replay_engine(1, decode_tokens=decode_tokens), [i])
        assert len(solo[i]) == 1 + decode_tokens
        np.testing.assert_array_equal(together[i], solo[i])


def test_service_measured_matches_closed_form():
    """Fig. 14/15 analog: data-plane AoPI ~= Theorems 1-2 prediction."""
    system = profiles.EdgeSystem(n_cameras=8, n_servers=2, n_slots=10,
                                 seed=3)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6)
    svc = AnalyticsService(ctrl, mode="mm1", epoch_duration=3000.0)
    reps = svc.run(3)
    for r in reps:
        assert r.measured_aopi == pytest.approx(r.predicted_aopi, rel=0.25)
    # per-stream agreement on average
    ratio = np.concatenate([r.per_stream_measured /
                            np.maximum(r.per_stream_predicted, 1e-9)
                            for r in reps])
    assert np.median(ratio) == pytest.approx(1.0, abs=0.15)


DEAD_SETS = [(0,), (1,), (2,), (0, 1), (0, 2), (1, 2)]


@pytest.mark.parametrize("dead_servers", DEAD_SETS,
                         ids=["-".join(map(str, d)) for d in DEAD_SETS])
def test_failover_reassigns_streams(dead_servers):
    system = profiles.EdgeSystem(n_cameras=9, n_servers=3, n_slots=5,
                                 seed=5)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6)
    dead = np.zeros(3, bool)
    dead[list(dead_servers)] = True
    rec = failover_assignment(ctrl, 0, dead)
    assert not dead[rec.assign].any()
