"""Scenario-suite quickstart: adversarial dynamics x every policy.

1. Build the full registered scenario suite (Gilbert-Elliott bursty
   channels, diurnal + flash-crowd load, server outages, camera SNR
   mobility, content bursts, plus the steady AR(1) anchor) as one stacked
   ``HorizonTables``.
2. Sweep LBCD and the MIN/DOS/JCAB baselines over the whole suite in one
   device-resident call per policy — shard_map-partitioned across every
   visible device (run with
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to watch the
   sharded path on CPU), vmapped on one.
3. With ``--dataplane``, additionally replay every (policy, scenario)
   pair through the batched device-resident GI/G/1 data plane
   (``repro.serving.replay``) so the report shows *measured* AoPI next to
   the closed-form prediction, plus their divergence. ``--delay-model``
   picks the delay family from ``queues.DELAY_MODELS`` (``mm1`` is the
   paper's exponential model; ``uniform``/``gamma``/``lognormal``/
   ``weibull`` are the §III-B regimes where Theorems 1-2 visibly drift),
   or ``auto`` to let the service fit the family from its own telemetry.
4. With ``--engine`` (implies ``--dataplane``), climb to the truth
   ladder's third rung: every cell is also driven through the real
   continuous-batching ``serving.Engine``, and the report grows
   engine columns with per-rung divergences (engine vs GI/G/1 vs
   closed form).
5. Print the per-family robustness report and each policy's worst family
   (and, with ``--dataplane``, its worst model-vs-measurement gap).

6. With ``--obs DIR`` (or ``REPRO_OBS_DIR``), stream spans/metrics from
   the whole run into ``DIR`` (``trace.jsonl``, ``metrics.prom``,
   ``metrics.jsonl``, Perfetto-loadable ``trace.json``) and print where
   they landed — ``python -m repro.obs.report DIR`` then shows
   plans/sec and p99 plan/replan latency per policy x family.

    PYTHONPATH=src python examples/scenario_suite.py \
        [--smoke] [--dataplane] [--engine] \
        [--engine-backend des|scan|auto] \
        [--delay-model mm1|uniform|gamma|lognormal|weibull|auto] \
        [--obs DIR]
"""
import argparse
from pathlib import Path

import jax

from repro import obs, scenarios, serving
from repro.core import queues


def main(smoke: bool = False, dataplane: bool = False,
         delay_model: str = "mm1", engine: bool = False,
         engine_backend: str = "scan", obs_dir: str | None = None):
    if obs_dir:
        obs.configure(run_dir=obs_dir)
    dataplane = dataplane or engine
    dims = (dict(n_cameras=6, n_slots=16, n_servers=2) if smoke
            else dict(n_cameras=16, n_slots=60, n_servers=3))
    s = scenarios.suite(**dims)
    print(f"suite: {s.n_scenarios} scenarios / "
          f"{len(set(s.families))} families -> {', '.join(s.names)}")

    dp_params = (dict(n_epochs=6, epoch_duration=400.0) if smoke
                 else dict(n_epochs=16, epoch_duration=600.0))
    dp_params["delay_model"] = delay_model
    if engine:
        dp_params["mode"] = "engine"
        if engine_backend == "des":
            # The DES pins one lane per stream and replays real decode
            # steps in Python, so bound its per-epoch work tightly.
            dp_params["engine_params"] = {"backend": "des",
                                          "frames_cap": 24 if smoke else 96}
        else:
            # The tick-scan backend replays the same engine as one
            # jitted lax.scan, so it runs at the full frames cap — the
            # effective per-epoch frame count is still sized by
            # queues.frames_budget from the offered load.
            dp_params["engine_params"] = {"backend": engine_backend}
        if smoke:
            dp_params["n_epochs"] = 3
            dp_params["epoch_duration"] = 120.0
    res = scenarios.sweep(s, v=10.0, p_min=0.7, dataplane=dataplane,
                          dataplane_params=dp_params)
    print(f"sweep backend: {res.backend} "
          f"({len(jax.devices())} visible device(s))"
          + (f"; data plane: {delay_model} x {dp_params['n_epochs']} "
             f"epochs" if dataplane else "")
          + (f"; rung 3: engine backend={engine_backend}" if engine
             else "") + "\n")

    rep = scenarios.robustness(res)
    print(rep)
    print()
    for policy in res.policies:
        fam, stats = rep.worst_family(policy)
        line = (f"{policy:<5s} worst family: {fam} "
                f"(worst-slot AoPI {stats.worst_aopi:.4f}, "
                f"p95 {stats.pct_aopi:.4f})")
        if dataplane:
            dfam, div = rep.worst_divergence(policy)
            line += f"; worst model-vs-measured gap: {dfam} ({div:+.2%})"
        print(line)
    if engine and rep.has_engine:
        print("\nengine rung present for all families:",
              all(rep.table[p][f].engine_mean is not None
                  for p in res.policies for f in rep.families))

    if obs_dir:
        paths = obs.write_artifacts(obs_dir)
        print(f"\nobs artifacts: {', '.join(sorted(paths.values()))}")
        print(f"dashboard: python -m repro.obs.report {obs_dir}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dimensions for CI smoke runs")
    ap.add_argument("--dataplane", action="store_true",
                    help="replay each (policy, scenario) through the "
                         "batched data plane for measured-vs-predicted "
                         "AoPI")
    ap.add_argument("--engine", action="store_true",
                    help="also drive every cell through the real "
                         "continuous-batching engine (truth ladder rung "
                         "3; implies --dataplane)")
    ap.add_argument("--engine-backend", default="scan",
                    choices=serving.ENGINE_BACKENDS,
                    help="engine-rung executor: 'scan' (default) is the "
                         "device-resident tick-scan at the full frames "
                         "cap; 'des' replays the real host Engine at a "
                         "tightly-bounded cap; 'auto' picks by epoch "
                         "frame volume")
    ap.add_argument("--delay-model", default="mm1",
                    choices=queues.DELAY_MODELS + (queues.AUTO_DELAY_MODEL,),
                    help="data-plane delay family (non-exponential models "
                         "show how far Theorems 1-2 drift); 'auto' fits "
                         "the family from service telemetry")
    ap.add_argument("--obs", default=None, metavar="DIR",
                    help="write repro.obs artifacts (trace.jsonl, "
                         "metrics.prom/jsonl, Perfetto trace.json) here")
    args = ap.parse_args()
    main(args.smoke, args.dataplane, args.delay_model, args.engine,
         args.engine_backend, args.obs)
