"""End-to-end serving driver (the paper's kind): a small LM serves batched
frame-analysis requests from multiple streams while the LBCD controller
adapts per-stream configuration (model/fidelity/policy) each epoch.

Two data planes:
  * default      — M/M/1 event-driven plane at the controller's chosen
                   rates (validates the closed forms at service scale);
  * --engine     — a REAL continuous-batching engine running a reduced
                   qwen2.5 on CPU with LCFSP preemption at step boundaries.

    PYTHONPATH=src python examples/serve_e2e.py [--engine] [--epochs 6]
"""
import argparse
from pathlib import Path

import numpy as np

from repro.core import lbcd, profiles
from repro.serving import AnalyticsService, Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--streams", type=int, default=12)
    args = ap.parse_args()

    system = profiles.EdgeSystem(
        n_cameras=args.streams, n_servers=2, n_slots=max(args.epochs, 8),
        mean_bandwidth_hz=12e6, mean_compute_flops=15e12, seed=0)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.7)

    if args.engine:
        import jax

        from repro import configs
        from repro.models import build
        from repro.models.common import init_params

        cfg = configs.get("qwen2.5-3b").reduced()
        model = build(cfg)
        params = init_params(model.template(), jax.random.PRNGKey(0))
        # The engine replay plane pins one lane per stream.
        eng = Engine(model, params, n_lanes=args.streams, max_len=96,
                     decode_tokens=2)
        svc = AnalyticsService(ctrl, mode="engine", engine=eng,
                               epoch_duration=3.0, engine_frames_cap=32)
    else:
        svc = AnalyticsService(ctrl, mode="mm1", epoch_duration=1500.0)

    print("epoch  predicted-AoPI  measured-AoPI  accuracy     q")
    for t in range(args.epochs):
        r = svc.run_epoch(t)
        print(f"{t:>5d}  {r.predicted_aopi:13.4f}  {r.measured_aopi:12.4f}"
              f"  {r.accuracy:8.3f}  {r.q:6.3f}")
    print(f"\nmean predicted {svc.mean_predicted:.4f} s | "
          f"mean measured {svc.mean_measured:.4f} s | "
          f"deviation {abs(svc.mean_predicted - svc.mean_measured) / max(svc.mean_measured, 1e-9):.1%}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
