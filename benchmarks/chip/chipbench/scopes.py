"""Per-scope device time and the program's host spans, from a profiler
trace (``.xplane.pb``).

Device scopes: the program names the branches of its step and round
programs with ``jax.named_scope``; the names reach each HLO op's
``op_name`` metadata, which the trace keeps as the ``tf_op`` stat of the
op's *event metadata* on each ``/device:TPU:<n>`` plane (with the op's
``program_id``). ``jax.profiler.ProfileData`` shows an op event's name but
not its metadata's stats, so ``op_metadata`` reads them from the raw file
with a protobuf wire-format reader (no generated classes needed) and
``reduce_profile`` joins them to ``ProfileData``'s op events by program
and name. A fusion carries its root op's ``op_name``.

An ``op_name`` is a path: ``jit(round_fn)/vmap()/while/body/closed_call/
jit(step)/jvp(online)/frozen/while/body/...``. Autodiff wraps the first
scope inside the differentiated function in its transforms, so the
forward pass reads ``jvp(online)`` and the backward pass
``transpose(jvp(online))``; the backward's remat recompute sits under the
transpose too (``.../checkpoint/rematted_computation/...``).

Host spans: the program's spans enter ``jax.profiler.TraceAnnotation``
while a profile records, so they are events of the ``/host:CPU`` plane,
named as the spans, on the device timeline's clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from chipbench import xplane

# the program's device scopes (docs/observability.md, "Device scopes")
SCOPES = frozenset({"online", "target", "align", "frozen", "trained",
                    "heads", "loss", "augment", "optimizer", "wire",
                    "fedavg", "calibrate"})
# the program's host spans (docs/observability.md, "Span tree")
PROGRAM_SPANS = frozenset({
    "run", "round", "download", "local_train", "calibrate",
    "resources.measure", "fl.sample", "engine.plan", "engine.dispatch",
    "engine.readback", "client.train", "aggregate", "wire.download",
    "wire.upload", "wire.upload.client"})
READBACK = "engine.readback"
ROUND_MODULE = "jit_round_fn"

_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


# ---------------------------------------------------------------------------
# protobuf wire format: XSpace > XPlane > event/stat metadata
# ---------------------------------------------------------------------------
def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for
    varint and fixed-width fields, a memoryview for length-delimited
    ones (sub-messages, strings), so skipping a field copies nothing."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield num, v


def _map_values(entries):
    """Values of a protobuf map<int64, message> (entry: key 1, value 2)."""
    for entry in entries:
        for num, v in _fields(entry):
            if num == 2:
                yield v


def _plane_metadata(fields):
    """{(program id, op event name): tf_op} of one plane's fields."""
    stat_names = {}
    for m in _map_values(v for num, v in fields if num == 5):
        f = dict(_fields(m))                 # XStatMetadata: id 1, name 2
        stat_names[f.get(1, 0)] = bytes(f.get(2, b"")).decode()
    ids = {k for k, name in stat_names.items()
           if name in ("tf_op", "program_id")}
    out = {}
    for m in _map_values(v for num, v in fields if num == 4):
        name, stats = "", {}
        for num, v in _fields(m):            # XEventMetadata
            if num == 2:
                name = bytes(v).decode()
            elif num == 5:                   # XStat
                sf = dict(_fields(v))
                sid = sf.get(1, 0)
                if sid not in ids:
                    continue
                if 5 in sf:                  # str_value
                    val = bytes(sf[5]).decode()
                elif 7 in sf:                # ref_value: an interned string
                    val = stat_names.get(sf[7], "")
                else:                        # uint64 / int64 value
                    val = sf.get(3, sf.get(4, 0))
                stats[stat_names[sid]] = val
        if "tf_op" in stats and "program_id" in stats:
            out[(int(stats["program_id"]), name)] = stats["tf_op"]
    return out


def op_metadata(path):
    """{device plane name: {(program id, op event name): tf_op}} of every
    ``/device:TPU:<n>`` plane of the trace file; the planes' event lines
    are skipped unread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:                         # XSpace.planes
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in fields if n == 2), "")
        if name.startswith(xplane.DEVICE_PREFIX):
            out[name] = _plane_metadata(fields)
    return out


# ---------------------------------------------------------------------------
# op_name paths
# ---------------------------------------------------------------------------
def op_path(tf_op):
    """``tf_op`` -> the op's ``op_name`` path: the stat is ``<op_name>:``
    (an empty op type after the colon); an op CSE merged from several
    keeps the first name of its ``;``-joined list."""
    return tf_op.rsplit(":", 1)[0].split(";", 1)[0]


def path_scopes(path):
    """The scope names on ``path``, transforms (``jvp``, ``transpose``,
    ``vmap``) stripped: ``transpose(jvp(online))`` is ``online``."""
    out = set()
    for part in path.split("/"):
        while (m := _TRANSFORM.match(part)):
            part = m.group(1)
        if part in SCOPES:
            out.add(part)
    return out


def in_backward(path):
    return any(p.startswith("transpose(") for p in path.split("/"))


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
@dataclass
class ScopeTrace:
    devices: int
    # {module: {op_name path: summed op self time, ns, all devices}}
    op_ns: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # each device's idle gaps: the holes between its busy intervals
    idle: List[List[Tuple[float, float]]] = field(default_factory=list)
    # the program's host spans: (start, end, name)
    spans: List[Tuple[float, float, str]] = field(default_factory=list)

    def scope_ns(self, module, keep):
        """Op self time per device in ``module`` over the paths ``keep``
        accepts."""
        return sum(v for p, v in self.op_ns.get(module, {}).items()
                   if keep(p)) / self.devices

    def scoped_share(self, module=ROUND_MODULE):
        """Share of ``module``'s op time whose path carries a scope."""
        total = self.scope_ns(module, lambda p: True)
        return self.scope_ns(module, path_scopes) / total if total else 0.0

    def exposed_ns(self):
        """Device-idle time per device during which the host was in a
        program span other than ``engine.readback``."""
        spans = xplane.union([(s, e) for s, e, _ in self.spans])
        readback = xplane.union([(s, e) for s, e, n in self.spans
                                 if n == READBACK])
        return sum(_overlap(gaps, spans) - _overlap(gaps, readback)
                   for gaps in self.idle) / self.devices

    def span_names(self):
        return {n for _, _, n in self.spans}


def _overlap(a, b):
    """Summed length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _module_of(ops, modules):
    """For each (start, end, name) op, the (program id, module name) of
    the module event that contains its start, else None."""
    out, j = [], 0
    modules = sorted(modules)
    for s, _, _ in ops:
        while j < len(modules) and modules[j][1] < s:
            j += 1
        m = modules[j] if j < len(modules) and modules[j][0] <= s else None
        out.append(m[2] if m else None)
    return out


_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")


def reduce_profile(planes, devices, metadata):
    """``ScopeTrace`` of the first ``devices`` TPU planes, with
    ``metadata`` from ``op_metadata``."""
    planes = list(planes)
    dev = sorted((p for p in planes
                  if p.name.startswith(xplane.DEVICE_PREFIX)),
                 key=lambda p: p.name)[:devices]
    if len(dev) < devices:
        raise ValueError(f"trace has {len(dev)} TPU planes, need {devices}")
    t = ScopeTrace(devices=devices)
    for pl in dev:
        meta = metadata.get(pl.name, {})
        ops, modules = [], []
        for ln in pl.lines:
            if ln.name == xplane.OPS_LINE:
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in ln.events]
            elif ln.name == xplane.MODULES_LINE:
                for ev in ln.events:
                    m = _PROGRAM.match(ev.name)
                    if m:
                        modules.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        (int(m.group(2)), m.group(1))))
        ops.sort(key=lambda x: (x[0], -x[1]))
        self_ns = xplane.self_times([(s, e, i)
                                     for i, (s, e, _) in enumerate(ops)])
        for i, ((_, _, name), mod) in enumerate(zip(ops,
                                                    _module_of(ops, modules))):
            if mod is None:
                continue
            pid, module = mod
            path = op_path(meta.get((pid, name), ""))
            paths = t.op_ns.setdefault(module, {})
            paths[path] = paths.get(path, 0.0) + self_ns[i]
        busy = xplane.union([(s, e) for s, e, _ in ops])
        t.idle.append([(e1, s2) for (_, e1), (s2, _) in zip(busy,
                                                            busy[1:])])
    for pl in planes:
        if pl.name == xplane.HOST_PLANE:
            for ln in pl.lines:
                t.spans += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in ln.events
                            if ev.name in PROGRAM_SPANS]
    return t


def reduce_trace(path, devices):
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)).planes, devices,
                          op_metadata(path))


# ---------------------------------------------------------------------------
# per-layer readings, in ms per window round
# ---------------------------------------------------------------------------
def _frozen(p):
    return {"online", "frozen"} <= path_scopes(p) and not in_backward(p)


READINGS = {
    # one copy of the stage's frozen-prefix forward: the online encoder's
    # [0, active_from) blocks over both views
    "engine.frozen_ms": (ROUND_MODULE, _frozen),
    # the target branch's forward (the target is never differentiated)
    "engine.target_ms": (ROUND_MODULE, lambda p: "target" in path_scopes(p)),
    "engine.align_ms": (ROUND_MODULE, lambda p: "align" in path_scopes(p)),
    # the backward pass, remat recompute included
    "engine.backward_ms": (ROUND_MODULE, in_backward),
    "engine.optimizer_ms": (ROUND_MODULE,
                            lambda p: "optimizer" in path_scopes(p)),
}


def readings(trace, rounds):
    """{metric: ms per round} of ``READINGS`` and ``driver.exposed_ms``;
    a reading with nothing to read (a program without the scopes or
    spans, a window without rounds) is left out."""
    if trace is None or not rounds:
        return {}
    out = {}
    for name, (module, keep) in READINGS.items():
        ns = trace.scope_ns(module, keep)
        if ns > 0:
            out[name] = 1e-6 * ns / rounds
    if READBACK in trace.span_names():
        out["driver.exposed_ms"] = 1e-6 * trace.exposed_ns() / rounds
    return out
