#!/usr/bin/env python3
"""Drive the AoPI control loop once on one TPU chip and check what it says.

    python chip_smoke.py [--seed N]      # one chip, phases 1-5
    python chip_smoke.py --chips 4       # the 4-chip scenario sweep only

One process drives the chip; every input is generated from ``--seed``.
Each phase prints one JSON line with its shapes, the solver backend as
resolved (interpret mode must be off), compile seconds (first call),
steady seconds (second call, ended by ``block_until_ready`` or a host
copy) and its parity numbers. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and is printed only when every phase passed on a TPU. Any failed check
raises, so the script exits non-zero without that line — also when JAX
finds no TPU, or when the ``src/`` tree of this checkout is missing.

Phases on one chip:
  1. device: a TPU is present and the Pallas kernels run compiled.
  2. paper: the paper's setting (30 cameras, 3 servers, 30 MHz and
     50 TFLOPS per server), LBCD under ``AnalyticsService(mode="mm1")``
     for one 8-epoch planning window; measured vs predicted AoPI.
  3. city: 10^4 cameras on 100 servers (per-server budgets x10, so each
     camera's share matches the paper's), ``solver_backend="auto"``
     (compiled Pallas kernels), two planning windows.
  4. parity: ``bcd.solve_slot`` on the chip at 10^4 cameras (whole-fleet
     kernels) and 10^5 (camera-tiled) against the jnp path on the CPU.
  5. engine: the engine rung (``mode="engine"``, tick-scan backend).
``--chips 4``:
  6. sweep: ``scenarios.sweep`` over every registered scenario family
     and all four policies at 300 cameras, ``backend="fleet"`` and
     ``"shard_map"`` on four chips against ``"vmap"`` on one.

``--rehearse`` runs the same phases at toy sizes on whatever backend JAX
has (the CPU, with Pallas in interpret mode) and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

FULL = dict(city_cameras=10_000, city_servers=100, city_epoch_s=20.0,
            city_frames_cap=4096, tiled_cameras=100_000, tiled_spec="auto",
            engine_streams=300, engine_servers=10, engine_frames_cap=4096,
            sweep_cameras=300, sweep_servers=10, sweep_slots=12)
TINY = dict(city_cameras=300, city_servers=3, city_epoch_s=20.0,
            city_frames_cap=512, tiled_cameras=2048,
            tiled_spec="pallas:tile=1024", engine_streams=12,
            engine_servers=3, engine_frames_cap=256, sweep_cameras=8,
            sweep_servers=2, sweep_slots=4)

#: The paper's per-server resources (§VI-A); the city fleet scales them
#: with the fleet so every camera's share stays the paper's.
PAPER_BW_HZ, PAPER_FLOPS, PAPER_CAMS_PER_SERVER = 30e6, 50e12, 10

#: Measured vs closed-form AoPI in the paper's M/M/1 setting: the
#: theorems are exact there, so only sampling noise of one 8-epoch
#: window separates them.
PAPER_MAX_DIVERGENCE = 0.05
#: Chip (compiled Pallas, f32) vs CPU (jnp reference) on one slot, both
#: at the converged "seed" solver effort: the dual search converges to
#: the same allocation up to f32 rounding of different op orders.
PARITY_RTOL = 1e-4
#: The bound the repository's own test holds the shard_map sweep to
#: (tests/test_scenarios.py): per-scenario horizon means.
SHARD_RTOL_AOPI, SHARD_RTOL_ACC = 0.08, 0.05


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def report(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def timed(fn):
    """``(result, seconds)`` of one call, waiting for the device."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready([x for x in jax.tree.leaves(out)
                           if isinstance(x, jax.Array)])
    return out, time.perf_counter() - t0


def backend_of(spec_str: str, n: int, masked: bool = False) -> dict:
    from repro.core import bcd
    from repro.kernels.slot_solver import ops
    spec = bcd.resolve_spec(spec_str, n, masked=masked)
    return {"solver_backend": spec.backend, "tile_n": spec.tile_n,
            "fuse": spec.fuse, "interpret": ops._resolve_interpret(None)}


def service_clean(svc, reps) -> dict:
    import numpy as np
    measured = np.array([r.measured_aopi for r in reps])
    predicted = np.array([r.predicted_aopi for r in reps])
    check(np.isfinite(measured).all() and np.isfinite(predicted).all(),
          "finite measured and predicted AoPI")
    check(not svc.plan_failures, f"no plan failures: {svc.plan_failures}")
    check(not svc.fallbacks, f"no fallbacks: {svc.fallbacks}")
    check(not svc.degraded_epochs, "no degraded epochs")
    return {"measured_aopi": float(measured.mean()),
            "predicted_aopi": float(predicted.mean()),
            "divergence": float(measured.mean() / predicted.mean() - 1.0),
            "plan_failures": len(svc.plan_failures),
            "fallbacks": len(svc.fallbacks)}


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_device(devices, on_tpu: bool):
    import jax
    from repro.kernels.slot_solver import ops
    interpret = ops._resolve_interpret(None)
    report("device", jax=jax.__version__, platform=devices[0].platform,
           kind=devices[0].device_kind, count=len(devices),
           interpret=interpret)
    if on_tpu:
        check(interpret is False, "pallas kernels compiled (interpret off)")


def phase_paper(seed: int):
    from repro.core import profiles
    from repro.core.lbcd import LBCDController
    from repro.serving import AnalyticsService

    def run():
        sys_ = profiles.EdgeSystem(seed=seed)
        svc = AnalyticsService(
            LBCDController(sys_, v=10.0, p_min=0.7, solver_backend="auto"),
            mode="mm1", plan_window=8, seed=seed)
        return svc, svc.run(8)

    (_, _), compile_s = timed(run)
    (svc, reps), steady_s = timed(run)
    stats = service_clean(svc, reps)
    check(abs(stats["divergence"]) <= PAPER_MAX_DIVERGENCE,
          f"paper-setting divergence {stats['divergence']:.4f} within "
          f"{PAPER_MAX_DIVERGENCE}")
    n = svc.controller.system.n_cameras
    report("paper", cameras=n, servers=svc.controller.system.n_servers,
           epochs=8, **backend_of("auto", n), compile_s=compile_s,
           steady_s=steady_s, **stats,
           divergence_bound=PAPER_MAX_DIVERGENCE)


def window_memory(n: int, frames: int) -> dict:
    """``memory_analysis()`` of one batched measurement dispatch of the
    city fleet: ``queues._window_sim`` at the epoch chunk the service
    uses, compiled for the default device."""
    import jax
    import jax.numpy as jnp
    from repro.core import queues
    from repro.serving import service
    e = max(service.MAX_BATCH_ELEMS // (n * frames), 1)
    dtype = (jnp.float64 if frames > queues.F32_MAX_FRAMES
             else jnp.float32)
    with jax.enable_x64(True):
        shapes = [jax.ShapeDtypeStruct((e, n), dtype)] * 3 + [
            jax.ShapeDtypeStruct((e, n), jnp.int32),
            jax.ShapeDtypeStruct((e,), jax.random.key(0).dtype)]
        mem = queues._window_sim.lower(
            *shapes, 1.0, frames, "mm1").compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    return {"epochs_per_dispatch": e, "frames": frames,
            "dtype": jnp.dtype(dtype).name, "lane_frames": e * n * frames,
            "temp_bytes": mem.temp_size_in_bytes, "total_bytes": total,
            "bytes_per_lane_frame": total / (e * n * frames)}


def phase_city(seed: int, size: dict):
    import jax
    from repro.core import profiles
    from repro.core.lbcd import LBCDController
    from repro.serving import AnalyticsService
    n, s = size["city_cameras"], size["city_servers"]
    scale = n / (s * PAPER_CAMS_PER_SERVER)
    sys_ = profiles.EdgeSystem(
        n_cameras=n, n_servers=s, n_slots=16,
        mean_bandwidth_hz=PAPER_BW_HZ * scale,
        mean_compute_flops=PAPER_FLOPS * scale, seed=seed)
    svc = AnalyticsService(
        LBCDController(sys_, v=10.0, p_min=0.7, solver_backend="auto"),
        mode="mm1", plan_window=8, seed=seed,
        epoch_duration=size["city_epoch_s"],
        frames_cap=size["city_frames_cap"])
    first, compile_s = timed(lambda: [svc.run_epoch(t) for t in range(8)])
    second, steady_s = timed(lambda: [svc.run_epoch(t)
                                      for t in range(8, 16)])
    stats = service_clean(svc, first + second)
    mem = window_memory(n, size["city_frames_cap"])
    hbm = jax.devices()[0].memory_stats() or {}
    report("city", cameras=n, servers=s, epochs=16, plan_window=8,
           budget_scale=scale, epoch_duration_s=size["city_epoch_s"],
           frames_cap=size["city_frames_cap"], **backend_of("auto", n),
           compile_s=compile_s, steady_s=steady_s, **stats,
           window_memory=mem,
           peak_bytes_in_use=hbm.get("peak_bytes_in_use"),
           bytes_limit=hbm.get("bytes_limit"))


def _slot_inputs(n: int, s: int, seed: int):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import profiles
    scale = n / (s * PAPER_CAMS_PER_SERVER)
    tab = profiles.EdgeSystem(
        n_cameras=n, n_servers=s, n_slots=1,
        mean_bandwidth_hz=PAPER_BW_HZ * scale,
        mean_compute_flops=PAPER_FLOPS * scale, seed=seed).horizon(1)
    sid = np.random.default_rng(seed).integers(0, s, n).astype(np.int32)
    return (np.asarray(tab.acc[0]), np.asarray(tab.xi),
            np.asarray(tab.size), np.asarray(tab.eff), sid,
            np.asarray(tab.budgets_b[0]), np.asarray(tab.budgets_c[0]),
            np.float32(1.3), np.float32(10.0))


def phase_parity(seed: int, size: dict):
    import jax
    import numpy as np
    from repro.core import bcd
    cpu = jax.devices("cpu")[0]
    for n, spec in ((size["city_cameras"], "auto"),
                    (size["tiled_cameras"], size["tiled_spec"])):
        s = size["city_servers"]
        host = _slot_inputs(n, s, seed)
        kw = dict(n_servers=s, solver_effort="seed")

        def chip():
            args = jax.device_put(host, jax.devices()[0])
            return bcd.solve_slot(*args, solver_backend=spec, **kw)

        dec, compile_s = timed(chip)
        dec, steady_s = timed(chip)
        with jax.default_device(cpu):
            ref, ref_s = timed(lambda: bcd.solve_slot(
                *jax.device_put(host, cpu), solver_backend="jnp", **kw))
        out = {}
        for f in ("r_idx", "m_idx", "pol"):
            diff = int((np.asarray(getattr(dec, f))
                        != np.asarray(getattr(ref, f))).sum())
            out[f"{f}_mismatches"] = diff
        for f in ("b", "c"):
            a, r = np.asarray(getattr(dec, f)), np.asarray(getattr(ref, f))
            out[f"{f}_max_rel"] = float(np.max(np.abs(a - r) /
                                               np.maximum(np.abs(r), 1e-30)))
        report("parity", cameras=n, servers=s, spec=spec,
               **backend_of(spec, n), effort="seed", compile_s=compile_s,
               steady_s=steady_s, cpu_jnp_s=ref_s, rtol=PARITY_RTOL, **out)
        check(all(out[f"{f}_mismatches"] == 0
                  for f in ("r_idx", "m_idx", "pol")),
              f"equal argmin indices at N={n}")
        check(out["b_max_rel"] <= PARITY_RTOL
              and out["c_max_rel"] <= PARITY_RTOL,
              f"b/c within rtol {PARITY_RTOL} at N={n}")


def phase_engine(seed: int, size: dict):
    from repro.core import profiles
    from repro.core.lbcd import LBCDController
    from repro.serving import AnalyticsService
    n, s = size["engine_streams"], size["engine_servers"]
    scale = n / (s * PAPER_CAMS_PER_SERVER)

    def run():
        sys_ = profiles.EdgeSystem(
            n_cameras=n, n_servers=s, n_slots=4,
            mean_bandwidth_hz=PAPER_BW_HZ * scale,
            mean_compute_flops=PAPER_FLOPS * scale, seed=seed)
        svc = AnalyticsService(
            LBCDController(sys_, v=10.0, p_min=0.7, solver_backend="auto"),
            mode="engine", engine_backend="scan", plan_window=4, seed=seed,
            epoch_duration=size["city_epoch_s"],
            frames_cap=size["engine_frames_cap"],
            engine_frames_cap=size["engine_frames_cap"])
        return svc, svc.run(4)

    _, compile_s = timed(run)
    (svc, reps), steady_s = timed(run)
    stats = service_clean(svc, reps)
    import numpy as np
    model = np.array([r.model_aopi for r in reps])
    check(np.isfinite(model).all(), "finite GI/G/1 rung")
    report("engine", streams=n, servers=s, epochs=4,
           engine_backend=svc.engine_backend,
           frames_cap=size["engine_frames_cap"], **backend_of("auto", n),
           compile_s=compile_s, steady_s=steady_s, **stats,
           gi_g1_aopi=float(model.mean()),
           engine_vs_gi=float(stats["measured_aopi"] / model.mean() - 1.0))


def phase_sweep(devices, seed: int, size: dict):
    import numpy as np
    from repro import scenarios
    n, s = size["sweep_cameras"], size["sweep_servers"]
    scale = n / (s * PAPER_CAMS_PER_SERVER)
    suite = scenarios.suite(
        n_cameras=n, n_servers=s, n_slots=size["sweep_slots"],
        mean_bandwidth_hz=PAPER_BW_HZ * scale,
        mean_compute_flops=PAPER_FLOPS * scale, seed=seed)
    runs = {}
    for backend, devs in (("vmap", devices[:1]), ("fleet", devices),
                          ("shard_map", devices)):
        def go():
            return scenarios.sweep(suite, backend=backend, devices=devs,
                                   solver_backend="auto")
        _, compile_s = timed(go)
        res, steady_s = timed(go)
        check(not res.errors, f"{backend} sweep errors: {res.errors}")
        runs[backend] = res
        report("sweep", backend=res.backend, devices=len(devs),
               scenarios=len(suite.names), families=len(set(suite.families)),
               policies=list(res.policies), cameras=n, servers=s,
               slots=size["sweep_slots"],
               **backend_of("auto", n, masked=suite.tables.active is not None),
               compile_s=compile_s, steady_s=steady_s,
               scenario_slots_per_s=len(suite.names) * size["sweep_slots"]
               * len(res.policies) / steady_s)
    ref, fleet, shard = runs["vmap"], runs["fleet"], runs["shard_map"]
    for p in ref.policies:
        bitwise = all(np.array_equal(getattr(fleet, k)[p], getattr(ref, k)[p])
                      for k in ("aopi", "acc", "q"))
        aopi_rel = float(np.max(np.abs(shard.mean_aopi(p) - ref.mean_aopi(p))
                                / ref.mean_aopi(p)))
        acc_rel = float(np.max(np.abs(shard.mean_acc(p) - ref.mean_acc(p))
                               / ref.mean_acc(p)))
        report("sweep_parity", policy=p, fleet_bitwise=bitwise,
               fleet_max_abs=float(np.max(np.abs(fleet.aopi[p]
                                                 - ref.aopi[p]))),
               shard_aopi_max_rel=aopi_rel, shard_acc_max_rel=acc_rel,
               bound_aopi=SHARD_RTOL_AOPI, bound_acc=SHARD_RTOL_ACC)
        check(bitwise, f"fleet == vmap bitwise for {p}")
        check(aopi_rel <= SHARD_RTOL_AOPI and acc_rel <= SHARD_RTOL_ACC,
              f"shard_map within the statistical bound for {p}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; prints no result line")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    compile_cache.enable(ROOT)
    size = TINY if args.rehearse else FULL

    phase_device(devices, on_tpu)
    if args.chips == 4:
        phase_sweep(devices, args.seed, size)
    else:
        phase_paper(args.seed)
        phase_city(args.seed, size)
        phase_parity(args.seed, size)
        phase_engine(args.seed, size)
    if on_tpu and not args.rehearse:
        print(json.dumps({"ok": True, "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
