"""Reduce a profiler trace (``.xplane.pb``) to device busy time, op time
by name and idle gaps by what the host was doing.

* Device planes are the planes named ``/device:<KIND>:<i>`` (a chip;
  not ``/device:CUSTOM:...``). On each, op events are those of the lines
  named ``XLA Ops`` (every line where there is none); program events
  those of the lines named ``XLA Modules``. An op's name is its HLO
  instruction's name (``%fusion.12``), the text before `` = ``.
* Busy time is the union of a device's op intervals inside the window;
  the window is the host annotation ``window_name`` (the whole trace
  where it is absent). Where the device's trace buffers overflowed (a
  ``Trace Buffers Dropped`` event), the window ends where the dropping
  began, so it covers only what the trace holds.
* An idle gap is a stretch of the window in which no device runs an op.
  Each of 1 us or more is attributed to the innermost host event (the
  shortest one on a host plane) that covers its midpoint, other than the
  window itself; shorter ones are summed under ``SHORT_GAPS``.
"""
from __future__ import annotations

import collections
import dataclasses
import re

import numpy as np

HOST_PLANE_PREFIX = "/host:"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DROPPED = "Trace Buffers Dropped"
NO_HOST_SPAN = "(no host span)"
#: Gaps shorter than this (between ops of one program) are summed apart
#: under SHORT_GAPS, not attributed to a host span one by one.
MIN_GAP_NS = 1000.0
SHORT_GAPS = "(gaps under 1 us)"


@dataclasses.dataclass
class Reduction:
    window_s: float
    clipped: bool           # the device dropped events; window cut there
    busy_s: dict            # device plane -> busy seconds in the window
    op_s: dict              # op name -> seconds, summed over devices
    module_s: dict          # program name -> seconds, summed over devices
    idle_by_host: dict      # host event name -> idle seconds (all devices)
    programs: list = dataclasses.field(default_factory=list)
    # ^ (name, seconds) of each program run wholly inside the window, in
    #   the order the device ran them

    @property
    def mean_busy_s(self) -> float:
        return (sum(self.busy_s.values()) / len(self.busy_s)
                if self.busy_s else 0.0)

    def top_ops(self, k: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]

    def top_idle(self, k: int = 10) -> list:
        return sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:k]


def _events(line):
    """A line's events as (names, start ns, end ns)."""
    names, start, dur = [], [], []
    for e in line.events:
        names.append(e.name)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    start = np.asarray(start, np.float64)
    return names, start, start + np.asarray(dur, np.float64)


def _union(start, end):
    """Merged, disjoint intervals of ``[start, end)`` pairs, sorted."""
    if not start.size:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    first = np.flatnonzero(np.r_[True, start[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, start.size - 1]
    return start[first], reach[last]


def _innermost(host, times) -> list:
    """Name of the shortest host event covering each time."""
    names, start, end = host
    if not names:
        return [NO_HOST_SPAN] * len(times)
    dur = end - start
    out = []
    for i in range(0, len(times), 64):
        t = np.asarray(times[i:i + 64])[:, None]
        cover = (start[None, :] <= t) & (t <= end[None, :])
        best = np.where(cover, dur[None, :], np.inf).argmin(axis=1)
        out += [names[j] if cover[k, j] else NO_HOST_SPAN
                for k, j in enumerate(best)]
    return out


def _by_name(names, seconds, into: collections.Counter) -> None:
    """Add ``seconds`` per event to ``into`` under each short name."""
    ids, short = [], {}
    for n in names:
        ids.append(short.setdefault(n, len(short)))
    totals = np.bincount(np.asarray(ids, np.int64), weights=seconds,
                         minlength=len(short))
    for n, i in short.items():
        into[n.split(" = ", 1)[0]] += float(totals[i])


def reduce_planes(planes, window_name: str | None = None) -> Reduction:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` carrying ``name``, ``start_ns`` and
    ``duration_ns`` (``jax.profiler.ProfileData``'s planes)."""
    host = ([], np.empty(0), np.empty(0))
    devices, dropped = {}, []
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith(HOST_PLANE_PREFIX):
            for ln in lines:
                n, s, e = _events(ln)
                host = (host[0] + n, np.r_[host[1], s], np.r_[host[2], e])
        elif DEVICE_PLANE.match(plane.name):
            names = {ln.name for ln in lines}
            ops, mods = ([], np.empty(0), np.empty(0)), None
            for ln in lines:
                n, s, e = _events(ln)
                dropped += [s[i] for i, x in enumerate(n) if x == DROPPED]
                if ln.name == MODULES_LINE:
                    mods = (n, s, e)
                elif ln.name == OPS_LINE or OPS_LINE not in names:
                    keep = [i for i, x in enumerate(n) if x != DROPPED]
                    ops = (ops[0] + [n[i] for i in keep],
                           np.r_[ops[1], s[keep]], np.r_[ops[2], e[keep]])
            devices[plane.name] = (ops, mods or ([], np.empty(0),
                                                 np.empty(0)))
    hn, hs, he = host
    win = [i for i, n in enumerate(hn) if n == window_name]
    if win:
        lo, hi = hs[win].min(), he[win].max()
    else:
        every_s = np.concatenate([hs] + [x[1] for d in devices.values()
                                         for x in d])
        every_e = np.concatenate([he] + [x[2] for d in devices.values()
                                         for x in d])
        lo = every_s.min() if every_s.size else 0.0
        hi = every_e.max() if every_e.size else 0.0
    clipped = bool(dropped) and min(dropped) < hi
    if clipped:
        hi = max(min(dropped), lo)
    others = [i for i, n in enumerate(hn) if n != window_name]
    host = ([hn[i] for i in others], hs[others], he[others])
    busy, op_s, module_s = {}, collections.Counter(), collections.Counter()
    idle, programs = collections.Counter(), []
    for plane, ((on, os_, oe), (mn, ms, me)) in devices.items():
        _by_name(on, np.clip(np.minimum(oe, hi) - np.maximum(os_, lo), 0,
                             None) * 1e-9, op_s)
        _by_name(mn, np.clip(np.minimum(me, hi) - np.maximum(ms, lo), 0,
                             None) * 1e-9, module_s)
        whole = np.flatnonzero((ms >= lo) & (me <= hi))
        programs += [(mn[i], ms[i], (me[i] - ms[i]) * 1e-9) for i in whole]
        inside = (np.minimum(oe, hi) > np.maximum(os_, lo))
        bs, be = _union(np.maximum(os_[inside], lo), np.minimum(oe[inside], hi))
        busy[plane] = float((be - bs).sum()) * 1e-9
        gs = np.r_[lo, be]
        ge = np.r_[bs, hi]
        gap = ge - gs
        short = (gap > 0) & (gap < MIN_GAP_NS)
        if short.any():
            idle[SHORT_GAPS] += float(gap[short].sum()) * 1e-9
        wide = np.flatnonzero(gap >= MIN_GAP_NS)
        who = _innermost(host, list(0.5 * (gs[wide] + ge[wide])))
        for name, g in zip(who, gap[wide]):
            idle[name] += float(g) * 1e-9
    return Reduction(window_s=(hi - lo) * 1e-9, clipped=clipped, busy_s=busy,
                     op_s=dict(op_s), module_s=dict(module_s),
                     idle_by_host=dict(idle),
                     programs=[(n, d) for n, _, d in sorted(
                         programs, key=lambda x: x[1])])


def reduce_file(path: str, window_name: str | None = None) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window_name)
