"""The EUA deployment (``eua-816``) on the CPU, at a small fleet of its
shape: 136 cameras on 21 servers, the fewest cameras above
``bcd.AUTO_PALLAS_MIN_CAMERAS`` that keep EUA's 6.5 cameras a server,
planned 2 slots at a time, so ``solver_backend="auto"`` plans with the Pallas
slot solver (in interpret mode).

* The replan loop, and a serve loop whose plan window the data plane
  takes in several dispatches, pass the cell's limits.
* Faults planted on the Pallas path come out not correct.
* ``bench/roofline.py`` counts what the program's kernels move.
* The kernel readers read a trace, and nothing without one.

Importing this module also sizes ``eua-816`` for the harness's tests of
every cell (``test_harness.py``, ``test_control.py``), which run each
cell's own traffic at its configuration's toy size. Those runs plan on
the jnp path: ``control.FAULTS["bandwidth_short"]`` patches
``allocate.waterfill_bandwidth``, which the Pallas path never calls, so
the harness's fault tests cannot reach the Pallas planner; the tests
below hold it to the same check.
"""
import collections
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_harness
from bench import control, harness, loops, roofline

CONFIG = harness.load_json(harness.BENCH / "configs" / "eua-816.json")
LIMITS = harness.load_json(harness.BENCH / "limits" / "eua-816.replan.json")
#: The data plane's limits, which hold per lane at any fleet size.
PLANE_LIMITS = {k: v for k, v in harness.load_json(
    harness.BENCH / "limits" / "paper-30.serve.json").items()
    if k in ("measured_aopi_gap", "count_gap")}

SHAPE = dict(n_cameras=136, n_servers=21, frames_cap=4096)
test_harness.TOY.setdefault("eua-816", {**SHAPE, "solver_backend": "jnp"})

#: Plan windows of 2 slots on a 4-slot horizon (a serve loop whose
#: horizon is one window would keep its first plan); 25 cycles of it
#: bring the virtual queue to its steady cycle, as the cell's 24 cycles
#: of 64 slots do.
HORIZON = dict(horizon_slots=4, plan_window=2, warm_cycles=25)
SEED = 2**31 + 11


def _traffic(name):
    return {**harness.load_json(harness.BENCH / "traffic" / f"{name}.json"),
            **HORIZON}


def _run(loop, seconds=0.3):
    loop.setup()
    rec = loop.window(seconds)
    loop.release()
    assert rec["attempted"] > 0
    return rec, loop.check()


def _over(gaps, limits):
    return {k for k, v in gaps.items() if not v <= limits[k]}


@pytest.fixture(autouse=True)
def _short_check(monkeypatch):
    """Check two plans or plan windows, not the cell's nine or three."""
    monkeypatch.setattr(loops.Replan, "CHECK_PLANS", 1)
    monkeypatch.setattr(loops.Serve, "CHECK_WINDOWS", 1)


def test_replan_on_the_pallas_path_passes_the_cell_limits():
    from repro import obs
    obs.reset()
    loop = loops.Replan({**CONFIG, **SHAPE}, _traffic("replan"), SEED)
    rec, gaps = _run(loop)
    assert rec["failed"] == 0
    assert set(gaps) == set(LIMITS) and not _over(gaps, LIMITS), gaps
    dispatch = [e["args"] for e in obs.events()
                if e["name"] == "planner.dispatch"]
    assert dispatch and all(
        (a["backend"], a["n_cameras"], a["n_servers"]) == ("pallas", 136, 21)
        for a in dispatch)


def test_serve_with_a_window_in_several_dispatches_passes_the_limits(
        monkeypatch):
    from repro import obs
    from repro.serving import service
    # Room for one epoch a dispatch, as at 816 cameras and 40,960 frames.
    monkeypatch.setattr(service, "MAX_BATCH_ELEMS",
                        SHAPE["n_cameras"] * SHAPE["frames_cap"])
    obs.reset()
    loop = loops.Serve({**CONFIG, **SHAPE}, _traffic("serve"), SEED)
    rec, gaps = _run(loop)
    assert rec["failed"] == 0
    limits = {**LIMITS, **PLANE_LIMITS}
    assert set(gaps) == set(limits) and not _over(gaps, limits), gaps
    inputs = [e["args"]["dispatches"] for e in obs.events()
              if e["name"] == "data_plane.inputs"]
    calls = [e for e in obs.events() if e["name"] == "queues.gi_g1_window"]
    assert inputs and set(inputs) == {HORIZON["plan_window"]}
    assert len(calls) == sum(inputs)


def _short_fill():
    """The fused water-fill hands out 90% of the bandwidth it solved for."""
    from repro.kernels import slot_solver
    pair = slot_solver.waterfill_pair

    def short(*args, **kwargs):
        b, c = pair(*args, **kwargs)
        return 0.9 * b, c
    return [(slot_solver, "waterfill_pair", short)]


@pytest.mark.parametrize("fault,gap", [("argmin_shift", "config_miss"),
                                       ("short_fill", "budget_gap")])
def test_a_fault_on_the_pallas_path_is_not_correct(fault, gap, monkeypatch):
    patches = (control.FAULTS[fault][0]() if fault in control.FAULTS
               else _short_fill())
    for obj, attr, new in patches:
        monkeypatch.setattr(obj, attr, new)
    jax.clear_caches()
    try:
        _, gaps = _run(loops.Replan({**CONFIG, **SHAPE}, _traffic("replan"),
                                    SEED))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert gap in _over(gaps, LIMITS), gaps


def test_roofline_counts_match_a_hand_count():
    traffic = harness.load_json(harness.BENCH / "traffic" / "replan.json")
    # config_argmin at 816 cameras: the [816, 9, 6] accuracy block, b, c
    # and link efficiency in, three index rows out, the [9, 6] FLOPs
    # table, 6 frame sizes, q and V: 49,022 words.
    assert roofline.config_argmin_bytes(816, 9, 6) == 4 * (
        816 * 54 + 3 * 816 + 3 * 816 + 54 + 6 + 2) == 196_088
    # waterfill_pair: 816 cameras pad to 896 lanes; eight vectors in, two
    # out, the [125, 896] membership, the FCFS margin.
    assert roofline.waterfill_pair_bytes(816, 125) == 4 * (
        10 * 896 + 125 * 896 + 1) == 483_844
    assert roofline.waterfill_pair_bytes(816, 1) == 39_428
    # A plan of 8 slots, two solves a slot, 4 passes each (and a final
    # water-fill a solve).
    assert roofline.plan_bytes(CONFIG, traffic) == {
        "config_argmin": 8 * 2 * 4 * 196_088,
        "waterfill_pair": 8 * 5 * (39_428 + 483_844)}


def _kernel_calls(jaxpr, mult=1, out=None):
    """``{(kernel name, operand shapes): calls}`` of every ``pallas_call``
    one run of ``jaxpr`` makes, a scan's body counted once a step."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            shapes = tuple(tuple(v.aval.shape)
                           for v in (*eqn.invars, *eqn.outvars))
            out[eqn.params["name"], shapes] += mult
        step = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, mult * step, out)
    return out


def test_roofline_counts_the_kernel_calls_of_a_plan():
    """The plan's kernel calls, counted in the program's own ``rollout``,
    are those ``plan_bytes`` counts; each call's least bytes are at most
    what its operands and results hold."""
    from repro.core import lbcd
    from repro.core.profiles import HorizonTables
    n, s, k = SHAPE["n_cameras"], SHAPE["n_servers"], HORIZON["plan_window"]
    m, r = CONFIG["models"], len(CONFIG["resolutions"])

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)
    tables = HorizonTables(acc=f32(k, n, m, r), xi=f32(m, r), size=f32(r),
                           eff=f32(n), budgets_b=f32(k, s),
                           budgets_c=f32(k, s))
    jaxpr = jax.make_jaxpr(lambda t: lbcd.rollout(
        t, 10.0, 0.7, n_bcd_iters=CONFIG["bcd_iters"],
        solver_backend="auto"))(tables)
    calls = _kernel_calls(jaxpr.jaxpr)
    want = collections.Counter()
    for (name, shapes), count in calls.items():
        if name == roofline.OPS["config_argmin"]:
            least = roofline.config_argmin_bytes(n, m, r)
            kernel = "config_argmin"
        else:
            assert name == roofline.OPS["waterfill_pair"]
            least = roofline.waterfill_pair_bytes(n, shapes[9][0])
            kernel = "waterfill_pair"
        assert least <= 4 * sum(int(np.prod(x)) for x in shapes)
        want[kernel] += count * least
    assert dict(want) == roofline.plan_bytes(
        {**CONFIG, **SHAPE}, {"plan_window": k})


def _traced(op_s, programs, plans):
    trace = SimpleNamespace(op_s=op_s, programs=programs)
    return SimpleNamespace(
        trace=trace, record={"plans": plans}, cfg=CONFIG,
        traffic=harness.load_json(harness.BENCH / "traffic" / "replan.json"),
        peaks=harness.peaks_for("TPU v5 lite"))


#: Three plans traced: each rollout program 50 ms, 2 ms of it the config
#: search, 3 ms the fused water-fill (op names as the compiled HLO has them).
TRACE = ({"%slot_solver.config_argmin.14": 0.004,
          "%slot_solver.config_argmin.15": 0.002,
          "%slot_solver.waterfill_pair.22": 0.009, "%fusion.7": 0.1},
         [("jit_rollout", 0.05)] * 3, 3)


@pytest.mark.parametrize("metric,want", [
    ("kernels.config_argmin_ms.eua", 2.0),
    ("kernels.waterfill_ms.eua", 3.0),
    ("kernels.config_argmin.roofline.eua",
     100 * 64 * 196_088 / 819e9 / 0.002),
    ("kernels.waterfill.roofline.eua",
     100 * 40 * (39_428 + 483_844) / 819e9 / 0.003),
    ("planner.device_ms.eua", 50.0),
])
def test_kernel_reader_reads_a_trace(metric, want):
    assert harness.reader(metric)(_traced(*TRACE)) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "kernels.config_argmin_ms.eua", "kernels.waterfill_ms.eua",
    "kernels.config_argmin.roofline.eua", "kernels.waterfill.roofline.eua",
    "planner.device_ms.eua", "device_idle.eua-replan"])
def test_reader_reads_nothing_without_a_trace(metric):
    run = _traced(*TRACE)
    run.trace = None
    assert harness.reader(metric)(run) is None


@pytest.mark.parametrize("metric", [
    "kernels.config_argmin_ms.eua", "kernels.waterfill_ms.eua",
    "kernels.config_argmin.roofline.eua", "kernels.waterfill.roofline.eua"])
def test_kernel_reader_reads_nothing_without_named_kernels(metric):
    """A program whose kernels carry no ``name=`` reads nothing."""
    op_s = {"%custom-call.3": 0.006, "%fusion.7": 0.1}
    assert harness.reader(metric)(_traced(op_s, *TRACE[1:])) is None
