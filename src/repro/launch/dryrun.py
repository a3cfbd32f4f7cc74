import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell: lower + compile the
production step with explicit shardings, record memory_analysis() and
cost_analysis(), and parse collective bytes from the optimized HLO.

Accounting notes (see EXPERIMENTS.md §Dry-run):
  * cost_analysis() on this backend reports **per-device** numbers and
    counts a lax.scan (while-loop) body ONCE. The production step scans
    over layer periods, so we compile the cell at period depth 1 and 2 and
    extrapolate linearly: total = f(1) + (n_periods - 1) * (f(2) - f(1)).
    Verified exact vs an unrolled compile for small configs
    (tests/test_dryrun_accounting.py).
  * The einsum ("ref") attention path materializes score tensors that the
    Pallas flash kernels never do; memory is reported both raw and with the
    analytic score-bytes correction.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out results/dryrun
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import numpy as np

from .. import configs
from ..configs.base import ALL_SHAPES, shape_supported
from .mesh import make_production_mesh
from .specs import plan_cell

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|u32|s8|u8|pred|s64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
          "s8": 1, "u8": 1, "pred": 1, "s64": 8}


def _type_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.groups()
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-op-type result bytes of every collective instruction."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)", s)
        if not m:
            continue
        rhs = m.group(1)
        for op in COLLECTIVES:
            # match the op name as the instruction (not in metadata)
            if re.search(rf"\b{op}(?:-start|-done)?\(", rhs):
                # result type(s) = text before the op name
                head = rhs.split(op)[0]
                out[op] += _type_bytes(head)
                counts[op] += 1
                break
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _reduced_depth(cfg, n_periods: int):
    """Config with the layer stack cut to n_periods periods."""
    from ..models.transformer import layout
    period, full = layout(cfg)
    plen = len(period)
    ch = {"n_layers": plen * n_periods}
    if cfg.enc_layers:
        ch["enc_layers"] = n_periods
        ch["n_layers"] = n_periods
    return dataclasses.replace(cfg, **ch), full


def measure_cell(cfg, shape, mesh, *, skip_extrapolation=False,
                 **plan_kwargs) -> dict:
    """Compile a cell and return the full accounting dict. ``plan_kwargs``
    (impl, mlstm_impl, rule_overrides, n_microbatches, ...) forward to
    plan_cell — the hillclimb harness varies them per iteration."""
    rec = {"arch": cfg.name, "shape": shape.name,
           "mesh": tuple(mesh.shape.values()),
           "n_devices": int(np.prod(list(mesh.shape.values())))}
    t0 = time.time()
    plan = plan_cell(cfg, shape, mesh, **plan_kwargs)
    lowered = plan.lower()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_gib": ma.argument_size_in_bytes / 2**30,
        "output_gib": ma.output_size_in_bytes / 2**30,
        "temp_gib": ma.temp_size_in_bytes / 2**30,
        "alias_gib": ma.alias_size_in_bytes / 2**30,
    }
    ca = compiled.cost_analysis()
    rec["cost_full_hlo"] = {"flops": ca.get("flops", 0.0),
                            "bytes": ca.get("bytes accessed", 0.0)}
    rec["collectives_full_hlo"] = collective_bytes(compiled.as_text())
    rec["n_microbatches"] = getattr(plan, "n_microbatches", None)

    if skip_extrapolation:
        return rec

    # Two-depth extrapolation for scan-body accounting.
    from ..models.transformer import layout
    _, n_full = layout(cfg)
    vals = {}
    for depth in (1, 2):
        dcfg, _ = _reduced_depth(cfg, depth)
        # Probes run a single microbatch (= the full token count in one
        # unrolled pass) so the grad-accumulation scan cannot hide FLOPs;
        # memory realism comes from the full compile above, not the probes.
        probe_kwargs = dict(plan_kwargs)
        probe_kwargs["n_microbatches"] = 1
        dplan = plan_cell(dcfg, shape, mesh, **probe_kwargs)
        dcomp = dplan.lower().compile()
        dca = dcomp.cost_analysis()
        vals[depth] = {
            "flops": dca.get("flops", 0.0),
            "bytes": dca.get("bytes accessed", 0.0),
            "coll": collective_bytes(dcomp.as_text())["total_bytes"],
        }
    rec["extrapolated"] = {}
    for key in ("flops", "bytes", "coll"):
        slope = vals[2][key] - vals[1][key]
        rec["extrapolated"][key] = float(
            vals[1][key] + (n_full - 1) * slope)
    rec["depth_probe"] = vals
    rec["n_periods"] = n_full
    return rec


def iter_cells(arch_sel, shape_sel):
    for name, cfg in configs.ARCHS.items():
        if arch_sel != "all" and name != arch_sel:
            continue
        for shape in ALL_SHAPES:
            if shape_sel != "all" and shape.name != shape_sel:
                continue
            ok, reason = shape_supported(cfg, shape)
            if not ok:
                yield cfg, shape, reason
            else:
                yield cfg, shape, None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--impl", default="ref")
    ap.add_argument("--fast", action="store_true",
                    help="skip depth extrapolation probes")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    n_ok = n_skip = n_fail = 0
    for cfg, shape, skip_reason in iter_cells(args.arch, args.shape):
        for mesh_name, mesh in meshes:
            cell = f"{cfg.name}__{shape.name}__{mesh_name}"
            path = os.path.join(args.out, cell + ".json")
            if skip_reason:
                rec = {"arch": cfg.name, "shape": shape.name,
                       "mesh": mesh_name, "skipped": skip_reason}
                n_skip += 1
                print(f"SKIP {cell}: {skip_reason}", flush=True)
            else:
                try:
                    rec = measure_cell(cfg, shape, mesh, impl=args.impl,
                                       skip_extrapolation=args.fast)
                    rec["mesh_name"] = mesh_name
                    n_ok += 1
                    print(f"OK   {cell}: compile={rec['compile_s']}s "
                          f"flops={rec['extrapolated']['flops'] if 'extrapolated' in rec else rec['cost_full_hlo']['flops']:.3e} "
                          f"coll={rec['collectives_full_hlo']['total_bytes']:.3e}B "
                          f"temp={rec['memory']['temp_gib']:.1f}GiB",
                          flush=True)
                except Exception as e:
                    rec = {"arch": cfg.name, "shape": shape.name,
                           "mesh": mesh_name, "error": str(e),
                           "traceback": traceback.format_exc()}
                    n_fail += 1
                    print(f"FAIL {cell}: {e}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}", flush=True)


if __name__ == "__main__":
    main()
