"""The benchmark harness on the CPU: every cell runs end to end at a toy
size, the measurement path refuses to run
without a TPU, ``BENCHMARK.json`` keeps to its format, and the peaks table
refuses a device it does not know."""
import json
import os
import re
import subprocess
import sys
import time

import pytest

from bench import harness, loops

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

#: Toy sizes of each configuration: the same code paths (more than 1024
#: frames keep the data plane in float64), small enough for the CPU.
TOY = {"paper-30": dict(frames_cap=4096)}


def run_toy(workload, seed=3, seconds=1.0, trace=False, control=False,
            memory_report=False):
    """One toy run of a cell; returns its result line as a dict."""
    cell = harness.find(SPEC["workloads"], workload)
    lines = []

    class Out:
        def write(self, s):
            lines.append(s)

        def flush(self):
            pass

    rc = harness.run(workload, seed, seconds, trace,
                     t_start=time.perf_counter(), require_chip=False,
                     config_overrides=TOY[cell["config"]], control=control,
                     memory_report=memory_report, out=Out())
    assert rc == 0
    return json.loads("".join(lines).strip().splitlines()[-1])


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_runs_end_to_end_and_is_correct(workload):
    line = run_toy(workload)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line["checks"]
    want = {m["name"] for m in harness.metrics_of(SPEC, workload,
                                                  "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    for check in line["checks"].values():
        assert check["limit"] is not None
        assert 0 <= check["value"] <= check["limit"]


def test_a_traced_run_reports_per_layer_metrics_only():
    line = run_toy("paper-30.serve", trace=True)
    names = set(line["metrics"])
    allowed = {m["name"] for m in harness.metrics_of(SPEC, "paper-30.serve",
                                                     "per_layer")}
    # The CPU trace has no device plane, so the device readers find
    # nothing; the span readers do.
    assert {"service.self_ms.serve", "planner.ms.serve"} <= names <= allowed
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "paper-30.serve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_device_kind_has_no_peaks():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        harness.peaks_for("TPU v99")


def test_benchmark_json_names_units_and_files():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert len(names) == len(set(names))
    for e in SPEC["configs"]:
        assert NAME.match(e["name"])
        assert all(NAME.match(k) for k in e["reduced"])
        cfg = json.loads((ROOT / e["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(e["reduced"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (harness.BENCH / "configs" / f"{w['config']}.json").exists()
        traffic = harness.load_json(harness.BENCH / "traffic"
                                    / f"{w['traffic']}.json")
        assert traffic["entry"] in loops.ENTRIES
        assert (harness.BENCH / "limits" / f"{w['name']}.json").exists()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_each_per_layer_metric_moves_a_metric_its_cells_report(metric):
    m = harness.find(SPEC["per_layer"], metric)
    moved = harness.find(SPEC["end_to_end"], m["moves"])
    for cell in m["workloads"]:
        assert cell in moved.get("workloads", [cell])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(SPEC, w["name"],
                                                     "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(SPEC, w["name"], "per_layer")


def test_memory_report_names_the_window_programs(capsys):
    run_toy("paper-30.serve", memory_report=True)
    err = capsys.readouterr().err
    assert "memory rollout: argument=" in err
    assert "memory _window_sim[8x30x4096]: argument=" in err
    assert "temp=" in err
