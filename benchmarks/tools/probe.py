"""Sizing probe: which batch of each training cell compiles and runs on the chip.

    python3 benchmarks/tools/probe.py            # parent: stays off jax
    python3 benchmarks/tools/probe.py --child <cell> <batch>

For each cell the parent tries the batches in the order given, one child
process per try (a process that has touched jax holds the chip), three steps
each, and stops at the first that runs. Each child prints one JSON line:
batch, peak_bytes_in_use, cold compile seconds, the attention route
(``tpu_custom_call`` count in the lowered step) and a step time. Each child
also records a three-step profiler trace and writes what its planes, lines and
events are called to ``chiprun_out/``: look at one by hand before writing
code against it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

TRIES = [("gpt2s-train-s1024", [32, 24, 16]),
         ("mistral7b-l2-train-s4096", [4, 3, 2, 1])]


def child(cell_name: str, batch: int, dump_trace: bool) -> int:
    import jax

    from benchmarks.harness import spec as _spec
    from benchmarks.harness import trace as _trace
    from benchmarks.harness.watch import compile_watch
    from benchmarks.runners import train as runner

    bench = _spec.load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench.cell(cell_name)
    cell.params["batch"] = batch
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"probe needs a TPU, found {dev.platform}")
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    t0 = time.perf_counter()
    prog = runner.build_program(bench, cell, seed=1234)
    build_s = time.perf_counter() - t0
    feed = runner.batch_fn(bench, cell, seed=1234)
    route = runner.route_of(prog, feed(0))
    with compile_watch() as watch:
        t1 = time.perf_counter()
        losses, _ = runner.drive(prog, feed, 2, steps=1)
        cold_s = time.perf_counter() - t1
    t2 = time.perf_counter()
    more, _ = runner.drive(prog, feed, 2, steps=3)
    losses += more
    step_s = (time.perf_counter() - t2) / 3
    out = {"cell": cell_name, "batch": batch, "build_s": round(build_s, 2),
           "first_step_s": round(cold_s, 2), "compiles": watch["compiles"],
           "cache_hits": watch["cache_hits"], "step_s": round(step_s, 4),
           "mosaic_calls": route, "losses": [float(v) for v in losses],
           "peak_bytes_in_use": dev.memory_stats()["peak_bytes_in_use"],
           "bytes_limit": dev.memory_stats().get("bytes_limit")}
    if dump_trace:
        tdir = os.path.join(ROOT, "chiprun_out", "probe_trace")
        jax.profiler.start_trace(tdir)
        runner.drive(prog, feed, 2, steps=3)
        jax.profiler.stop_trace()
        path = _trace.find_xplane(tdir)
        desc = _trace.describe(path)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"trace_desc_{cell_name}.json"), "w") as f:
            json.dump(desc, f, indent=1)
        events = _trace.load_events(path)
        _trace.save_events(events, os.path.join(
            ROOT, "chiprun_out", f"trace_events_{cell_name}.json.gz"))
        out["trace_lines"] = {k: len(v) for k, v in events.items()}
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
    print("PROBE " + json.dumps(out), flush=True)
    return 0


def main(argv) -> int:
    if argv and argv[0] == "--child":
        return child(argv[1], int(argv[2]), len(argv) > 3)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    results = []
    for cell_name, batches in TRIES:
        for b in batches:
            cmd = [sys.executable, os.path.abspath(__file__), "--child",
                   cell_name, str(b), "trace"]
            t0 = time.time()
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=1500)
            line = [ln for ln in p.stdout.splitlines()
                    if ln.startswith("PROBE ")]
            print(f"== {cell_name} batch {b}: rc={p.returncode} "
                  f"wall={time.time() - t0:.0f}s", flush=True)
            if p.returncode == 0 and line:
                print(line[-1], flush=True)
                results.append(json.loads(line[-1][6:]))
                break
            print(p.stderr[-3000:], flush=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
