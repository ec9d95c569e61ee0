"""Reduction of a profiler trace to device busy time, idle gaps and kernel
times.

``extract`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` writes into
a plain record (``Trace``): per device, the intervals of its XLA operations;
on the host, the benchmark's own ``TraceAnnotation`` spans (``bench.*``).
Everything after that works on the record alone, so the reduction is tested
on a small recorded trace without a chip.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os

HOST_PREFIX = "bench."          # the harness's own host spans
STEP_SPAN = "bench.step"        # one training step, batch to fetched loss
DEVICE_OPS_LINE = "XLA Ops"     # the line of a device plane that holds ops


@dataclasses.dataclass
class Trace:
    # device name -> [(op name, start ns, duration ns)]
    devices: dict
    # [(span name, start ns, duration ns)] of the harness's host spans
    host: list

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(e) for e in d["host"]])

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path) as fh:
            return cls.from_json(json.load(fh))


def op_name(event_name: str) -> str:
    """The HLO instruction of a device op: the TPU trace names an op by
    its whole instruction text, ``%name = shape op(operands)``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def extract(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, host, host_ops = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        host_ops.append((e.name, int(e.start_ns),
                                         int(e.duration_ns)))
    if not devices and host_ops:
        # XLA:CPU runs its ops on host threads: a CPU rehearsal of the
        # harness reads them as one device.  A benchmark run never gets
        # here, since it refuses to start without a TPU.
        devices["/host:CPU"] = sorted(host_ops, key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return Trace(devices, host)


def window(trace: Trace) -> tuple:
    """(start, end) ns of the traced window: first to last step span."""
    steps = [e for e in trace.host if e[0] == STEP_SPAN]
    if not steps:
        raise ValueError("no step span in the trace")
    return steps[0][1], max(s + d for _, s, d in steps)


def _merged(intervals, lo, hi):
    """Union of [start, end) intervals clipped to [lo, hi), sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which at least one op ran."""
    return sum(e - s for s, e in _merged(
        ((s, s + d) for _, s, d in events), lo, hi))


def idle_gaps(events, lo: int, hi: int) -> list:
    """[(start, end)] of [lo, hi) in which no op ran."""
    gaps, cur = [], lo
    for s, e in _merged(((s, s + d) for _, s, d in events), lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def busy_seconds(trace: Trace) -> float:
    """Busy time in the window, averaged over the traced devices."""
    lo, hi = window(trace)
    per = [busy_ns(ev, lo, hi) for ev in trace.devices.values()]
    if not per:
        raise ValueError("no device plane in the trace")
    return sum(per) / len(per) / 1e9


def window_seconds(trace: Trace) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def op_seconds(trace: Trace, match) -> float:
    """Seconds of ops whose name satisfies ``match``, inside the window,
    averaged over the traced devices."""
    lo, hi = window(trace)
    per = []
    for ev in trace.devices.values():
        per.append(sum(d for name, s, d in ev
                       if match(name) and s >= lo and s + d <= hi))
    return sum(per) / max(len(per), 1) / 1e9


def op_events(trace: Trace, match) -> list:
    """[(name, seconds)] of every op in the window whose name satisfies
    ``match``, on every device."""
    lo, hi = window(trace)
    return [(name, d / 1e9) for ev in trace.devices.values()
            for name, s, d in ev if match(name) and s >= lo and s + d <= hi]


def leaf_ops(events) -> list:
    """The ops that contain no other op: a ``while`` or ``call`` op's event
    spans the ops of its body, which are events of their own."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def host_label(trace: Trace, t: int) -> str:
    """The innermost harness span (other than the step) open at ``t``."""
    best = None
    for name, s, d in trace.host:
        if name != STEP_SPAN and s <= t < s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "between steps"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ops that took most device time (leaf ops, summed by name), and
    the longest idle gaps named by what the host was doing in them; both
    per device on average."""
    lo, hi = window(trace)
    n_dev = max(len(trace.devices), 1)
    tot = collections.Counter()
    gaps = []
    for ev in trace.devices.values():
        for name, s, d in leaf_ops(ev):
            if s >= lo and s + d <= hi:
                tot[name] += d
        for s, e in idle_gaps(ev, lo, hi):
            gaps.append((host_label(trace, (s + e) // 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, t / n_dev / 1e9]
                           for n, t in tot.most_common(top)],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
