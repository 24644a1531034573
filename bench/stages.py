"""The program's own spans and stages in a traced trial.

The program marks its host work with ``fl.`` spans and its round programs'
ops with ``fl.`` stage scopes (``repro.federated.spans``). This module reads
them from the profiler's trace beside what ``trace.py`` reads, into events
of the same form with these additions:

* ``op`` events carry a sixth field, the op's stage: the first ``fl.``
  component of its scope path (the ``op_name`` of its HLO metadata), or
  ``""``. ``ProfileData`` does not expose the HLO the trace keeps, so
  ``op_stages`` reads it from the ``.xplane.pb`` file itself;
* ``span`` events take names starting ``fl.`` as well as ``bench.``;
* ``launch`` events are the runtime's program launches on the host
  (``PJRT_LoadedExecutable_Execute``, one per program run, whatever
  the number of chips it runs on).

``reduce`` returns ``trace.reduce`` of the harness's view of the events,
every key and value as it is, plus

* ``stages``: per stage, the union of its ops' device intervals in the
  trial, in seconds (averaged over chips). A ``while`` op spans its body's
  ops, so a union and not a sum;
* ``spans``: per ``fl.`` span name, ``[count, total_s, self_s, busy_s]``:
  self is the time outside the ``fl.`` spans nested in it, busy the device
  time inside it;
* ``programs_by_span``: per ``fl.`` span name, the programs launched inside
  its spans. Launches, not module starts on the device: a module starts a
  varying time after its launch, so a count of starts moves by a few
  programs at a span's edges;
* ``idle_by_stage``: the device's idle time by the innermost span, of
  either prefix, in force at each idle gap's midpoint.

The metric readers get this reduction from ``of(run)``, which finds the
trace the harness left for the run's cell.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os

from bench import spec, trace

STAGE_PREFIX = "fl."
SPAN_PREFIXES = (trace.SPAN_PREFIX, STAGE_PREFIX)
LAUNCH = "PJRT_LoadedExecutable_Execute"
METADATA = "/host:metadata"


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized protobuf
    message: an int for a varint, a memoryview for a length-delimited
    field, None for a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stage(path: str) -> str:
    return next((p for p in path.split("/") if p.startswith(STAGE_PREFIX)),
                "")


def _module_stages(hlo) -> dict:
    """``{instruction: stage}`` of one serialized HloProto (1 hlo_module;
    HloModuleProto: 3 computations; HloComputationProto: 2 instructions;
    HloInstructionProto: 1 name, 7 metadata; OpMetadata: 2 op_name)."""
    out = {}
    for field, module in _fields(hlo):
        if field != 1:
            continue
        for field, comp in _fields(module):
            if field != 3:
                continue
            for field, inst in _fields(comp):
                if field != 2:
                    continue
                name, stage = "", ""
                for f, v in _fields(inst):
                    if f == 1:
                        name = _text(v)
                    elif f == 7:
                        stage = _stage(next((_text(x) for g, x in _fields(v)
                                             if g == 2), ""))
                if stage:
                    out[name] = stage
    return out


def op_stages(path) -> dict:
    """``{module: {instruction: stage}}`` from an ``.xplane.pb``: the HLO of
    each program the trace saw, kept as a bytes stat of the ``XLA Modules``
    event's metadata on the ``/host:metadata`` plane (XSpace: 1 planes;
    XPlane: 2 name, 4 event_metadata; XEventMetadata: 2 name, 5 stats;
    XStat: 6 bytes). The ops' own ``tf_op`` stats leave out ``while`` loops
    and kernels' custom calls; the HLO keeps every op's scope."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for f, v in fields if f == 2), "") != METADATA:
            continue
        for f, entry in fields:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next((_text(v) for g, v in meta if g == 2), "")
            for g, stat in meta:
                hlo = dict(_fields(stat)).get(6) if g == 5 else None
                if hlo is not None:
                    out[name] = _module_stages(hlo)
    return out


def events_from_profile(profile, stages=None) -> list:
    """Compact events of a ``jax.profiler.ProfileData``; ``stages`` is
    ``op_stages`` of the same trace (None: every op's stage is ``""``)."""
    stages = stages or {}
    out = []
    for plane in profile.planes:
        dev = trace.DEVICE_PLANE.match(plane.name)
        if dev is None:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIXES):
                            out.append(["span", -1, e.name, e.start_ns,
                                        e.end_ns])
                        elif e.name == LAUNCH:
                            out.append(["launch", -1, e.name, e.start_ns,
                                        e.end_ns])
            continue
        chip = int(dev.group(1))
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = [["module", chip, e.name, e.start_ns, e.end_ns]
                   for e in lines.get("XLA Modules", [])]
        out.extend(modules)
        starts = [m[3] for m in modules]
        for e in lines.get("XLA Ops", []):
            name = trace.op_name(e.name)
            i = bisect.bisect_right(starts, e.start_ns) - 1
            table = stages.get(modules[i][2], {}) if i >= 0 else {}
            out.append(["op", chip, name, e.start_ns, e.end_ns,
                        table.get(name, "")])
    return out


def load_xplane(path) -> list:
    from jax.profiler import ProfileData

    return events_from_profile(ProfileData.from_file(str(path)),
                               op_stages(path))


def harness_view(events) -> list:
    """The events as ``trace.events_from_profile`` gives them."""
    return [e[:5] for e in events if e[0] in ("op", "module")
            or (e[0] == "span" and e[2].startswith(trace.SPAN_PREFIX))]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo
            and s < hi]


def reduce(events) -> dict:
    """``trace.reduce`` of the harness's view, plus the program's stages
    and spans (see the module doc; seconds)."""
    red = trace.reduce(harness_view(events))
    spans = [(e[2], e[3], e[4]) for e in events if e[0] == "span"]
    _, t0, t1 = min((s for s in spans if s[0] == trace.TRIAL_SPAN),
                    key=lambda s: s[1])
    chips = sorted({e[1] for e in events if e[0] in ("op", "module")})
    busy, stages = {}, {}
    for chip in chips:
        ops = [e for e in events if e[0] == "op" and e[1] == chip]
        busy[chip] = trace.union(_clip([(e[3], e[4]) for e in ops], t0, t1))
        for stage in {e[5] for e in ops if len(e) > 5 and e[5]}:
            merged = trace.union(_clip(
                [(e[3], e[4]) for e in ops if len(e) > 5 and e[5] == stage],
                t0, t1))
            stages[stage] = stages.get(stage, 0.0) + sum(
                e - s for s, e in merged) / 1e9 / len(chips)

    mine = [s for s in spans if s[0].startswith(STAGE_PREFIX)
            and s[1] >= t0 and s[2] <= t1]
    launches = sorted(e[3] for e in events if e[0] == "launch")
    table, programs = {}, {}
    for i, (name, s, e) in enumerate(mine):
        inner = trace.union([(cs, ce) for j, (_, cs, ce) in enumerate(mine)
                             if j != i and s <= cs and ce <= e])
        dev = sum(trace.covered(b, s, e) for b in busy.values())
        row = table.setdefault(name, [0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
        row[2] += (e - s - trace.covered(inner, s, e)) / 1e9
        row[3] += dev / len(chips) / 1e9 if chips else 0.0
        programs[name] = programs.get(name, 0) + (
            bisect.bisect_right(launches, e) - bisect.bisect_left(launches, s))

    idle = {}
    if chips:
        merged = busy[chips[0]]
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = trace._label(spans, (s + e) / 2)
                idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
    return dict(red, stages=stages, spans=table,
                programs_by_span=programs if launches else {},
                idle_by_stage=idle)


def _cell_name(run) -> str | None:
    """The cell of BENCHMARK.json whose configuration and traffic ``run``
    holds."""
    root = spec.ROOT
    bench = spec.load_benchmark(root)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        traffic = root / "bench" / "traffic" / f"{w['traffic']}.json"
        if (spec.load_json(root / files[w["config"]]) == run["config"]
                and spec.load_json(traffic) == run["traffic"]):
            return w["name"]
    return None


@functools.lru_cache(maxsize=1)
def _reduction(path: str, mtime_ns: int) -> dict:
    return reduce(load_xplane(path))


def of(run):
    """The reduction of the trace that the harness left for ``run``'s cell
    in ``bench/.trace/<cell>``, or None: no traced trial in this run, or no
    such trace. Every reader of a run asks; the trace is read once."""
    if run.get("trace") is None:
        return None
    cell = _cell_name(run)
    if cell is None:
        return None
    paths = sorted(glob.glob(str(spec.ROOT / "bench" / ".trace" / cell
                                 / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not paths:
        return None
    return _reduction(paths[-1], os.stat(paths[-1]).st_mtime_ns)
