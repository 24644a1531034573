"""Profiler trace of one trial, and its reduction to per-layer numbers.

``capture`` runs a function under ``jax.profiler.trace`` and turns the
``.xplane.pb`` it writes into a compact list of events, each
``[kind, chip, name, start_ns, end_ns]``:

* ``op``: an operation on a chip's ``XLA Ops`` line;
* ``module``: a compiled program on a chip's ``XLA Modules`` line;
* ``span``: a host span of the harness's own (name starting ``bench.``).

``reduce`` takes such a list and the window of the first ``bench.trial``
span, and returns the device-busy union, the time of each op name and of
each module, and the idle gaps, each put down to the innermost harness
span in force at its midpoint. Readers in ``bench/metrics`` take their
numbers from this reduction.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINE_KINDS = {"XLA Ops": "op", "XLA Modules": "module"}
HLO_NAME = re.compile(r"^%?([^\s=]+) = ")
SPAN_PREFIX = "bench."
TRIAL_SPAN = "bench.trial"


def op_name(raw: str) -> str:
    """An op event's HLO instruction name: the TPU trace names each op by
    its whole instruction text (``%fusion.12 = f32[...] fusion(...)``)."""
    m = HLO_NAME.match(raw)
    return m.group(1) if m else raw


def events_from_profile(profile) -> list:
    """Compact events of a ``jax.profiler.ProfileData``."""
    out = []
    for plane in profile.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None:
                kind = LINE_KINDS.get(line.name)
                if kind is None:
                    continue
                chip = int(dev.group(1))
                name = op_name if kind == "op" else str
                out.extend([kind, chip, name(e.name), e.start_ns, e.end_ns]
                           for e in line.events)
            elif plane.name.startswith("/host:"):
                out.extend(["span", -1, e.name, e.start_ns, e.end_ns]
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return out


def capture(fn, logdir):
    """``(fn(), events)`` with ``fn`` run under the profiler. ``logdir`` is
    emptied first; the raw trace stays there."""
    import jax
    from jax.profiler import ProfileData

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(str(logdir)):
        result = fn()
    paths = sorted(glob.glob(os.path.join(str(logdir), "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {logdir}")
    return result, events_from_profile(ProfileData.from_file(paths[-1]))


def save(events, path):
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(events, f)


def load(path):
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(merged, lo, hi, starts=None) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover; ``starts``
    is ``[s for s, _ in merged]``, passed when calling many times."""
    if starts is None:
        starts = [s for s, _ in merged]
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def _label(spans, t) -> str:
    """The innermost harness span containing time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside"


def reduce(events) -> dict:
    """Per-layer numbers of the first ``bench.trial`` span (seconds)."""
    spans = [(e[2], e[3], e[4]) for e in events if e[0] == "span"]
    trials = [s for s in spans if s[0] == TRIAL_SPAN]
    if not trials:
        raise ValueError("the trace holds no bench.trial span")
    _, t0, t1 = min(trials, key=lambda s: s[1])
    chips = sorted({e[1] for e in events if e[0] in ("op", "module")})
    ops, modules, busy, idle, gaps = {}, {}, [], {}, []
    for chip in chips:
        mine = [(max(e[3], t0), min(e[4], t1), e[2]) for e in events
                if e[0] == "op" and e[1] == chip and e[4] > t0 and e[3] < t1]
        merged = union([(s, e) for s, e, _ in mine])
        starts = [s for s, _ in merged]
        busy.append(sum(e - s for s, e in merged))
        for s, e, name in mine:
            c = ops.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) / 1e9
        for e in events:
            if e[0] == "module" and e[1] == chip and e[4] > t0 and e[3] < t1:
                c = modules.setdefault(e[2], [0, 0.0])
                c[0] += 1
                c[1] += covered(merged, max(e[3], t0), min(e[4], t1),
                                starts) / 1e9
        if chip == chips[0]:
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    label = _label(spans, (s + e) / 2)
                    idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
                    gaps.append((e - s) / 1e9)
    window = (t1 - t0) / 1e9
    return {
        "window_s": window,
        "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
        "chips": len(chips),
        "ops": ops,
        "modules": modules,
        "idle_by_span": idle,
        "idle_gaps": len(gaps),
        "longest_gap_s": max(gaps, default=0.0),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time, and the idle time by the harness span the host was in."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[name, c[1]] for name, c in ops],
            "idle_gaps": [[name, s] for name, s in idle]}


def matching(table: dict, pattern: str) -> tuple:
    """(count, seconds) summed over the names that match ``pattern``."""
    rx = re.compile(pattern)
    hits = [c for name, c in table.items() if rx.search(name)]
    return sum(c[0] for c in hits), sum(c[1] for c in hits)
