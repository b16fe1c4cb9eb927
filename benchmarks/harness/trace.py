"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Two steps, so that the arithmetic can be tested without a chip:

1. ``load_events(path)`` reads the file with ``jax.profiler.ProfileData`` into
   plain data: ``{"<plane>|<line>": [[name, start_ns, duration_ns], ...]}`` for
   the device planes (names that start with ``/device:``).
2. ``reduce(events, step_pattern)`` works on that plain data alone. The traced
   window runs from the start of the first step program that the trace holds
   whole to the end of the last one (the first and the last program in the
   trace are taken as cut and left out); busy time is the union of the device-op
   intervals inside it, averaged over the chips that ran something.

On a TPU the device plane carries the lines "XLA Modules" (one event for each
run of a compiled program), "XLA Ops" (one for each operation in it) and
"Steps". Nothing here knows a kernel by name: a metric's reader brings its own
pattern.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str, planes_prefix: str = "/device:") -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(planes_prefix):
            continue
        for line in plane.lines:
            rows = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events]
            if rows:
                out[f"{plane.name}|{line.name}"] = rows
    return out


def describe(path: str, top: int = 25) -> dict:
    """What a trace's planes, lines and events are called: for a look by
    hand before code is written against it."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            total, count, stats = {}, 0, None
            for ev in line.events:
                count += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
                if stats is None:
                    stats = [[str(k), str(v)[:80]] for k, v in ev.stats][:12]
            names = sorted(total.items(), key=lambda kv: -kv[1])[:top]
            lines.append({"line": line.name, "events": count,
                          "first_event_stats": stats,
                          "top_by_time_ns": names})
        out.append({"plane": plane.name, "lines": lines})
    return {"file_bytes": os.path.getsize(path), "planes": out}


def save_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(ops, t0, t1):
    """Idle gaps inside [t0, t1] between (start, end, name) operations, each
    as (start, end, name of the operation that ended last before it, name of
    the one that starts after it); one sweep."""
    gaps, cur, last = [], t0, "window_start"
    for s, e, name in sorted(ops):
        if s > cur:
            gaps.append((cur, min(s, t1), last, name))
        if e > cur:
            cur, last = e, name
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1, last, "window_end"))
    return gaps


_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r"\)?\s([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """An "XLA Ops" event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...), kind=kLoop``). For a breakdown
    keep the result's name, the opcode and a custom call's target."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    out = head.lstrip("%")
    op = _OPCODE.search(rest)
    if op and op.group(1) not in out:
        out += " " + op.group(1)
    target = _TARGET.search(rest)
    if target:
        out += ":" + target.group(1)
    return out[:80]


def _device_planes(events: dict) -> dict:
    planes = {}
    for key, rows in events.items():
        plane, line = key.split("|", 1)
        planes.setdefault(plane, {})[line] = rows
    return planes


def reduce(events: dict, step_pattern: str) -> dict | None:
    """The traced window and what ran in it. Returns None where the trace
    holds no whole step program."""
    want = re.compile(step_pattern)
    per_chip = []
    for plane, lines in sorted(_device_planes(events).items()):
        mods = [r for r in lines.get(MODULE_LINE, []) if want.search(r[0])]
        ops = lines.get(OP_LINE, [])
        if not mods or not ops:
            continue
        mods.sort(key=lambda r: r[1])
        # a trace that starts or stops while a step runs holds that step in
        # part (its program event is cut, some of its ops are missing): the
        # first and the last step program are never counted
        mods = mods[1:-1]
        if not mods:
            continue
        t0 = mods[0][1]
        t1 = max(r[1] + r[2] for r in mods)
        inside = [(max(r[1], t0), min(r[1] + r[2], t1), r[0]) for r in ops
                  if r[1] + r[2] > t0 and r[1] < t1 and r[2] > 0]
        spans = [(s, e) for s, e, _ in inside]
        per_chip.append({
            "plane": plane, "steps": len(mods), "t0": t0, "t1": t1,
            "busy_ns": _union(spans),
            "ops": inside,
            "gaps": _gaps(inside, t0, t1)})
    if not per_chip:
        return None
    n = len(per_chip)
    first = per_chip[0]
    by_name = {}
    for s, e, name in first["ops"]:
        name = short_name(name)
        by_name[name] = by_name.get(name, 0.0) + (e - s)
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    # an idle gap is named by the operations on either side of it: the
    # host's spans are not on this clock yet (PERF.md, Open questions C)
    by_gap = {}
    for gs, ge, before, after in first["gaps"]:
        label = f"after {short_name(before)} before {short_name(after)}"
        by_gap[label] = by_gap.get(label, 0.0) + (ge - gs)
    top_gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n,
        "steps": first["steps"],
        "window_s": sum(c["t1"] - c["t0"] for c in per_chip) / n / 1e9,
        "busy_s": sum(c["busy_ns"] for c in per_chip) / n / 1e9,
        "ops": first["ops"],
        "device_ops": [[k, v / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v / 1e9] for k, v in top_gaps],
    }


def op_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Summed device time and count of the traced window's operations whose
    name matches ``pattern``."""
    want = re.compile(pattern)
    hits = [e - s for s, e, name in reduced["ops"] if want.search(name)]
    return sum(hits) / 1e9, len(hits)
