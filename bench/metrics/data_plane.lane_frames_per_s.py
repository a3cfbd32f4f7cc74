"""GI/G/1 data-plane throughput on the device, lane-frames/s: the
epochs x streams x frames of the window's ``queues.gi_g1_window`` calls
over the device time of their ``_window_sim`` programs.

Each call runs one such program, and both run in order, so the k-th
program in the trace is the k-th call's. Only programs that ran wholly
inside the traced window count (the trace may end early, where the
device dropped events), with the calls they belong to.
"""
PROGRAM = "_window_sim"


def read(run):
    if run.trace is None:
        return None
    runs = [s for name, s in run.trace.programs if PROGRAM in name]
    calls = sorted((e for e in run.spans if e["name"] == "queues.gi_g1_window"),
                   key=lambda e: e["ts"])
    if not runs or len(runs) > len(calls):
        return None
    work = sum(e["args"]["epochs"] * e["args"]["streams"]
               * e["args"]["n_frames"] for e in calls[:len(runs)])
    return work / sum(runs)
