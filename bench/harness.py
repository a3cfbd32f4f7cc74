"""Run one benchmark cell and print the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json`` (the deployment), ``traffic/<mix>.json``
(whose ``entry`` names its loop in ``loops.py``), ``limits/<cell>.json``
(the limit of each number the check compares) and, for every per-layer
metric, its reader ``metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import tempfile
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_SPAN = "bench.window"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries: list, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def peaks_for(kind: str) -> dict:
    """Published peaks of a device kind; an unknown kind is an error."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise ValueError(f"no peaks for device kind {kind!r}; known: "
                         f"{sorted(table)}")
    return table[kind]


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(spec: dict, workload: str, kind: str) -> list:
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``)."""
    return [m for m in spec[kind] if workload in m.get("workloads",
                                                       [workload])]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _compile_counter():
    """Count XLA compilations from now on (``count[0]``)."""
    from jax import monitoring
    count = [0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1
    monitoring.register_event_duration_secs_listener(listen)
    return count


def _memory_report(programs: list) -> None:
    """Print each program's compiled memory analysis (its temporaries are
    what the allocator's peak may not show)."""
    for name, lowered in programs:
        ma = lowered.compile().memory_analysis()
        if ma is None:
            continue
        _log(f"memory {name}: " + " ".join(
            f"{k}={getattr(ma, k + '_size_in_bytes', None)}"
            for k in ("argument", "output", "alias", "temp",
                      "generated_code")))


def _traced_window(loop, seconds: float):
    """Run the window under the profiler; returns the window record and
    the trace's reduction."""
    import jax
    from bench import trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as out:
        with jax.profiler.trace(out, profiler_options=opts):
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                rec = loop.window(seconds)
        files = sorted(Path(out).rglob("*.xplane.pb"))
        red = (trace_reduce.reduce_file(str(files[-1]), WINDOW_SPAN)
               if files else None)
    return rec, red


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        config_overrides: dict | None = None, control: bool = False,
        memory_report: bool | None = None, out=None) -> int:
    """Run one cell: set up, measure ``seconds``, check, print the line.

    Returns the exit code. Without a TPU holding the cell's chips it
    prints no result and returns 1; ``require_chip=False`` drives the
    rest of a run on whatever JAX has (tests, at toy sizes), and
    ``out`` takes the line in place of standard output. After the window
    it prints the compiled memory analysis of the programs the window
    drove (``memory_report``; by default where it needs a chip).
    """
    if memory_report is None:
        memory_report = require_chip
    spec = load_spec()
    cell = find(spec["workloads"], workload)
    cfg = {**load_json(BENCH / "configs" / f"{cell['config']}.json"),
           **(config_overrides or {})}
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits_path = BENCH / "limits" / f"{workload}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}

    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            _log(f"bench: no TPU (JAX sees {devices[0].platform}); "
                 "this benchmark measures the chip only")
            return 1
        if len(devices) < cell["chips"]:
            _log(f"bench: {workload} needs {cell['chips']} chips, JAX "
                 f"sees {len(devices)}")
            return 1
        peaks = peaks_for(devices[0].device_kind)
    else:
        peaks = None
    devices = devices[:cell["chips"]]
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache, obs
    from bench import loops
    if require_chip:
        # The compile cache lives in the checkout even where the
        # environment names another directory, so two checkouts never
        # share one; every program is cached, so a second run of a cell
        # compiles nothing.
        os.environ.pop(compile_cache.ENV_VAR, None)
        compile_cache.enable(ROOT)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    loop = loops.ENTRIES[traffic["entry"]](cfg, traffic, seed, control)
    loop.setup()
    compiles = _compile_counter()
    wall0 = time.time()
    setup_s = time.perf_counter() - t_start
    if trace:
        rec, red = _traced_window(loop, traffic["trace_seconds"])
    else:
        rec, red = loop.window(seconds), None
    wall1 = time.time()
    in_window = compiles[0]
    stats = [d.memory_stats() or {} for d in devices]
    mem = max(s.get("peak_bytes_in_use", 0) for s in stats)
    spans = [e for e in obs.events() if wall0 <= e["wall"] <= wall1]
    if memory_report:
        _memory_report(loop.programs(spans))
    loop.release()

    gaps = loop.check()
    # A gap that is not finite (a NaN or infinite answer) prints as the
    # largest double, so the line stays JSON; it fails any limit.
    checks = {k: {"value": v if math.isfinite(v) else sys.float_info.max,
                  "limit": limits.get(k)}
              for k, v in sorted(gaps.items())}
    correct = bool(checks) and rec["attempted"] > 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    if trace:
        ctx = types.SimpleNamespace(record=rec, spans=spans, trace=red,
                                    cfg=cfg, traffic=traffic, peaks=peaks)
        metrics = {}
        for m in metrics_of(spec, workload, "per_layer"):
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**rec, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(spec, workload, "end_to_end")}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    line = {"correct": correct, "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace and red is not None:
        device["busy_s"] = red.mean_busy_s
        device["window_s"] = red.window_s
        line["breakdown"] = {"device_ops": [list(x) for x in red.top_ops()],
                             "idle_gaps": [list(x) for x in red.top_idle()]}
    line["checks"] = checks

    _log(f"bench: {workload} seed={seed} window={rec['wall_s']:.3f}s "
         f"setup={setup_s:.3f}s compiles_in_window={in_window} "
         + " ".join(f"{k}={v}" for k, v in rec.items()
                    if k not in ("wall_s", "attempted", "failed")))
    _log(f"bench: attempted={rec['attempted']} failed={rec['failed']} "
         f"correct={correct}")
    for k, c in checks.items():
        _log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), file=out or sys.stdout, flush=True)
    return 0
