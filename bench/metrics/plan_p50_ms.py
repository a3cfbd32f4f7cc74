"""Median plan latency, ms: the same host-clock plan samples as
``plan_p95_ms`` (dispatch through every leaf copied to the host)."""


def read(run):
    return run.record.get("plan_p50_ms")
