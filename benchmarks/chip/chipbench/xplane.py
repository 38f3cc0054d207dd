"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Each TPU core is a plane named ``/device:TPU:<n>``. Its ``XLA Ops`` line
holds one event per executed HLO operation and its ``XLA Modules`` line
one event per executed program (``jit_<function name>(<id>)``). The host
is the ``/host:CPU`` plane; with the Python tracer on, its thread lines
hold the Python calls, on the same clock as the device events.

- busy time: the union of the op intervals of each device, averaged over
  the devices used;
- per-module time: the summed durations of a program's module events;
- per-op time: each HLO op's self time (a loop op keeps only what its
  body's ops leave uncovered), summed by instruction name;
- idle gaps: the holes in that union inside the traced span, each named
  by the most specific host event that covers most of it.
"""
from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(trace_dir):
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(event_name):
    """``jit_round_fn(1234)`` -> ``jit_round_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def self_times(events):
    """{op name: summed self time} of (start, end, name) events on one
    line, where an op that encloses others (a while loop around its body)
    keeps only the time none of them covers."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [end, name, child time, duration]

    def close(item):
        end, name, child, dur = item
        out[name] = out.get(name, 0.0) + dur - child
        if stack:
            stack[-1][2] += dur

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class DeviceTrace:
    devices: int
    busy_ns: float                       # mean over devices
    module_ns: Dict[str, float] = field(default_factory=dict)
    op_ns: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def busy_s(self):
        return self.busy_ns * 1e-9

    def module_s(self, pattern):
        """Seconds per device in modules whose name matches ``pattern``
        (a regular expression searched in the module name)."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_ns.items()
                   if rx.search(k)) * 1e-9 / self.devices

    def breakdown(self):
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v * 1e-9 / self.devices] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in self.gaps[:TOP]]}


def _host_events(planes):
    out = []
    for pl in planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def _name_gap(s, e, host):
    """The shortest host event that covers at least half of the gap, else
    the one that covers most of it."""
    best, best_cover = None, 0.0
    cands = []
    for hs, he, name in host:
        cover = min(e, he) - max(s, hs)
        if cover <= 0:
            continue
        cands.append((he - hs, cover, name))
        if cover > best_cover:
            best, best_cover = name, cover
    half = [c for c in cands if c[1] >= 0.5 * (e - s)]
    if half:
        return min(half)[2]
    return best or "no host event"


def reduce_profile(planes, devices):
    """``DeviceTrace`` of the first ``devices`` TPU planes."""
    planes = list(planes)
    dev = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                 key=lambda p: p.name)[:devices]
    if len(dev) < devices:
        raise ValueError(f"trace has {len(dev)} TPU planes, need {devices}")
    host = _host_events(planes)
    t = DeviceTrace(devices=devices, busy_ns=0.0)
    gaps = []
    for pl in dev:
        ops = []
        for ln in pl.lines:
            if ln.name == OPS_LINE:
                for ev in ln.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                op_name(ev.name)))
                for k, v in self_times(ops).items():
                    t.op_ns[k] = t.op_ns.get(k, 0.0) + v
            elif ln.name == MODULES_LINE:
                for ev in ln.events:
                    m = module_name(ev.name)
                    t.module_ns[m] = t.module_ns.get(m, 0.0) + ev.duration_ns
        busy = union([(s, e) for s, e, _ in ops])
        t.busy_ns += sum(e - s for s, e in busy) / devices
        gaps += [(s1, s2) for (_, s1), (s2, _) in zip(busy, busy[1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    t.gaps = [(_name_gap(s, e, host), e - s) for s, e in gaps[:TOP]]
    return t


def reduce_trace(path, devices):
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)).planes, devices)
