from .ops import (ServerLayout, baseline_argmax, config_argmin, first_fit,
                  server_layout, waterfill_bandwidth, waterfill_compute,
                  waterfill_pair)
from .ref import (baseline_argmax_ref, config_argmin_ref,
                  waterfill_bandwidth_ref, waterfill_compute_ref)

__all__ = ["ServerLayout", "server_layout", "config_argmin",
           "baseline_argmax", "waterfill_bandwidth", "waterfill_compute",
           "waterfill_pair", "first_fit", "config_argmin_ref",
           "baseline_argmax_ref", "waterfill_bandwidth_ref",
           "waterfill_compute_ref"]
