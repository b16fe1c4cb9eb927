"""Which program span was the host in when the device went idle —
``python -m tools.trace_gaps <profile dir or .xplane.pb>``.

``paddle_tpu.profiler.tracing`` enters a ``jax.profiler.TraceAnnotation`` for
every ``trace_span``/``trace_event``/``RecordEvent``, so a profile taken with
``jax.profiler.start_trace`` (or a ``Profiler`` with a non-CPU target) holds
the program's spans in its ``/host:CPU`` plane and the device's operations in
its ``/device:*`` planes, on one clock. This tool reads both and, for each
idle gap of the device above a threshold, prints the program span open on each
host thread at the gap's start, with totals per span name:

- **The window.** The step program is the compiled program the device spent
  most time in (``--program`` overrides: a regex on the "XLA Modules" names).
  Its first and last run in the trace are taken as cut by the trace's start
  and stop and left out; the window runs from the start of the first whole run
  to the end of the last, so it holds the gaps inside step programs and the
  ones between them.
- **A gap** is an interval of the window in which no "XLA Ops" event runs on
  that device.
- **A program span** is a host event whose name has the program's ``layer::what``
  form (``train::feed_wait``, ``train::dispatch``, ``train::fetch``,
  ``jit::compile``, ``decode::step``, ...). A ``jit::compile`` is stamped when
  the compile ends and carries its length as the stat ``duration_s``; it is
  widened back to where the compile began. Where spans nest, the innermost one
  open at the instant counts. A thread with none reads ``none``.

The arithmetic (``device_gaps``, ``open_spans``, ``attribute``) works on plain
lists so that it is tested without a chip; only ``load`` touches the file.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

__all__ = ["load", "origin_ns", "step_window", "device_gaps", "open_spans",
           "attribute", "report", "main"]

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
# the program's spans, and not the runtime's own C++ scopes
# (``PjRtCpuExecutable::Execute``), which the host plane also holds
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*::[a-z][a-z0-9_\[\]]*$")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def origin_ns(data) -> int | None:
    """The profile's origin on ``time.time_ns()``'s clock: the stat
    ``profile_start_time`` of the ``Task Environment`` plane of a
    ``jax.profiler.ProfileData``. ``origin + start_ns`` puts an event on
    the clock of the program's own stamps (``run_steps``' records)."""
    for plane in data.planes:
        if plane.name == "Task Environment":
            t = dict(plane.stats).get("profile_start_time")
            return None if t is None else int(t)
    return None


def load(path: str) -> dict:
    """The file as plain data: ``{"devices": {plane: {"modules": rows,
    "ops": rows}}, "host": {thread: rows}}`` with rows ``[name, start_ns,
    duration_ns]``; host rows are the program spans only."""
    import jax
    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    lines[line.name] = [
                        [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events]
            if lines.get(MODULE_LINE) and lines.get(OP_LINE):
                devices[plane.name] = {"modules": lines[MODULE_LINE],
                                       "ops": lines[OP_LINE]}
        elif plane.name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                rows = []
                for ev in line.events:
                    if not PROGRAM_SPAN.match(ev.name):
                        continue
                    start, dur = float(ev.start_ns), float(ev.duration_ns)
                    after = dict(ev.stats).get("duration_s")
                    if after is not None:       # stamped at its end
                        dur = float(after) * 1e9
                        start -= dur
                    rows.append([ev.name, start, dur])
                if rows:
                    host[f"{line.name}#{n}"] = rows
    return {"devices": devices, "host": host}


def step_window(modules: list, program: str | None = None):
    """(name, t0, t1, steps) of the step program's whole runs, or None where
    the trace holds fewer than three runs of it."""
    if program is not None:
        want = re.compile(program)
        modules = [r for r in modules if want.search(r[0])]
    total = {}
    for name, _, dur in modules:
        total[name] = total.get(name, 0.0) + dur
    if not total:
        return None
    name = max(total, key=total.get)
    runs = sorted((r for r in modules if r[0] == name),
                  key=lambda r: r[1])[1:-1]
    if not runs:
        return None
    return name, runs[0][1], max(r[1] + r[2] for r in runs), len(runs)


def device_gaps(ops: list, t0: float, t1: float) -> list:
    """Intervals of [t0, t1] in which no operation runs, each as ``(start,
    end, name of the operation that ended last before it, name of the one
    that starts after it)``."""
    gaps, cur, last = [], t0, "window_start"
    for name, start, dur in sorted(ops, key=lambda r: r[1]):
        end = start + dur
        if end <= t0 or dur <= 0:
            continue
        if start >= t1:
            break
        if start > cur:
            gaps.append((cur, start, last, name))
        if end > cur:
            cur, last = end, name
    if cur < t1:
        gaps.append((cur, t1, last, "window_end"))
    return gaps


def open_spans(host: dict, t: float) -> dict:
    """thread -> the innermost program span open at ``t`` (the one that
    started last among those that hold ``t``), or "none"."""
    out = {}
    for thread, rows in host.items():
        best = None
        for name, start, dur in rows:
            if start <= t < start + dur and (best is None
                                             or start >= best[1]):
                best = (name, start)
        out[thread] = best[0] if best else "none"
    return out


def attribute(gaps: list, host: dict) -> tuple[list, dict]:
    """Each gap with the span open on each thread at its start, and per span
    name the count and summed length of the gaps put down to it. A gap counts
    once for each distinct span name open at its start, and for "none" only
    where no thread had one open."""
    rows, totals = [], {}
    for start, end, before, after in gaps:
        spans = open_spans(host, start)
        names = sorted({s for s in spans.values() if s != "none"}) or ["none"]
        for n in names:
            count, ns = totals.get(n, (0, 0.0))
            totals[n] = (count + 1, ns + (end - start))
        rows.append({"start_ns": start, "length_ns": end - start,
                     "after_op": before, "before_op": after,
                     "spans": spans})
    return rows, totals


def _short(name: str) -> str:
    return name.partition(" = ")[0].lstrip("%")[:48]


def report(data: dict, program: str | None = None,
           min_us: float = 50.0) -> dict:
    """Gaps and totals per device plane of ``load``'s data."""
    out = {}
    for plane, lines in sorted(data["devices"].items()):
        win = step_window(lines["modules"], program)
        if win is None:
            continue
        name, t0, t1, steps = win
        every = device_gaps(lines["ops"], t0, t1)
        gaps = [g for g in every if g[1] - g[0] >= min_us * 1e3]
        rows, totals = attribute(gaps, data["host"])
        out[plane] = {
            "program": name, "steps": steps, "window_ns": t1 - t0, "t0": t0,
            "idle_ns": sum(g[1] - g[0] for g in every),
            "gaps": rows,
            "totals": {k: {"gaps": c, "ns": ns}
                       for k, (c, ns) in sorted(totals.items())}}
    return out


def _print(rep: dict, min_us: float, top: int) -> None:
    if not rep:
        print("no device plane with three runs of a step program")
    for plane, r in rep.items():
        steps = r["steps"]
        print(f"{plane}: {steps} whole runs of {r['program']}, window "
              f"{r['window_ns'] / 1e6:.3f} ms, idle "
              f"{r['idle_ns'] / 1e6:.3f} ms "
              f"({100 * r['idle_ns'] / r['window_ns']:.4f}%), "
              f"{len(r['gaps'])} gaps of at least {min_us:g} us")
        for g in sorted(r["gaps"], key=lambda g: -g["length_ns"])[:top]:
            spans = ", ".join(f"{t}: {s}" for t, s in sorted(
                g["spans"].items()) if s != "none") or "none"
            print(f"  +{(g['start_ns'] - r['t0']) / 1e6:11.3f} ms  "
                  f"{g['length_ns'] / 1e3:9.1f} us  after "
                  f"{_short(g['after_op'])} before "
                  f"{_short(g['before_op'])}  [{spans}]")
        for name, t in sorted(r["totals"].items(),
                              key=lambda kv: -kv[1]["ns"]):
            print(f"  total {name}: {t['gaps']} gaps, "
                  f"{t['ns'] / 1e6:.3f} ms, "
                  f"{t['ns'] / 1e6 / steps:.4f} ms a step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile", help="profile directory or .xplane.pb")
    ap.add_argument("--program", default=None,
                    help="regex on the step program's name (default: the "
                         "program with the most device time)")
    ap.add_argument("--min-us", type=float, default=50.0,
                    help="smallest gap listed and put down to a span")
    ap.add_argument("--top", type=int, default=40,
                    help="how many gaps to list, longest first")
    ap.add_argument("--json", default=None,
                    help="also write the full report to this file")
    args = ap.parse_args(argv)
    rep = report(load(args.profile), args.program, args.min_us)
    _print(rep, args.min_us, args.top)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f)
    return 0 if rep else 1


if __name__ == "__main__":
    sys.exit(main())
