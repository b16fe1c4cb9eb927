"""Shared hot-path model for the device-placement and recompile-hazard
passes.

"Hot path" means: code the steady-state training or serving loop runs
once per step/request, where a single silent host sync or retrace is
multiplied by the step count. The model is intra-module (graft_lint is
a per-file AST analyzer):

- A *hot module* is one of the subsystems whose whole job is the
  steady-state loop: ``paddle_tpu/serving/``, ``paddle_tpu/io/``,
  ``paddle_tpu/models/trainer.py``, and the repo-root ``bench*.py``
  files.
- Inside a hot module, the *roots* are the loop drivers
  (``run_steps``, the serving worker ``_run_loop``/``_execute``, the
  prefetch ``_produce``/``__next__``, queue ``next_batch``, client
  ``submit``/``run``); in a bench file every top-level function is a
  root (bench code is all timing loops).
- A function is *hot* when it is a root or reachable from one through
  the module's own call graph (plain-name and ``self.``-method calls),
  nested defs included.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Set, Tuple

HOT_MODULE_RES = (
    re.compile(r"(^|[\\/])paddle_tpu[\\/]serving[\\/]"),
    re.compile(r"(^|[\\/])paddle_tpu[\\/]io[\\/]"),
    re.compile(r"(^|[\\/])paddle_tpu[\\/]models[\\/]trainer\.py$"),
    # the GradScaler runs once per optimizer step by design — its
    # scale/unscale/update path is as hot as the step function itself
    re.compile(r"(^|[\\/])paddle_tpu[\\/]amp[\\/]__init__\.py$"),
    # resilience runs inside the training loop: maybe_save every step,
    # the write-behind worker concurrently with it, the Fs boundary on
    # every durable checkpoint byte
    re.compile(r"(^|[\\/])paddle_tpu[\\/]distributed[\\/]resilience[\\/]"),
    # the flight recorder is compiled into every serving/training hot
    # path: its record path (trace_span/trace_event -> ring push) runs
    # per request/step/token whenever tracing is on, and its background
    # flusher concurrently with everything
    re.compile(r"(^|[\\/])paddle_tpu[\\/]profiler[\\/]tracing\.py$"),
)

HOT_ROOT_NAMES = {"run_steps", "_run_loop", "_execute", "_produce",
                  "__next__", "next_batch", "submit", "run",
                  "step", "unscale_", "update",
                  # the decode scheduler's per-token loop: every decode
                  # subsystem function reachable from it (admit, prefill,
                  # decode step, emit) is per-step hot
                  "_step_loop",
                  # the serving router: dispatch workers run once per
                  # request (retries/failovers included) and the health
                  # prober once per backend per tick — both multiply any
                  # silent sync or retrace by the traffic rate
                  "_dispatch_loop", "_health_loop", "submit_decode",
                  # the wire transport: the client receiver demuxes one
                  # frame per token/reply, the host's accept/serve/relay
                  # loops run per connection and per streamed token, and
                  # the fault proxy's pump forwards every wire byte —
                  # all per-token/per-request hot
                  "_recv_loop", "_keepalive_loop", "_accept_loop",
                  "_serve_conn", "_relay_stream", "_await_oneshot",
                  "_pump",
                  # resilience: the per-step save gate, the write-behind
                  # worker loop, and the per-write fault/Fs boundary
                  "maybe_save", "save", "_write_loop", "poll",
                  "on_write",
                  # flight recorder (profiler/tracing.py): the record
                  # path runs inside every other hot loop, so its own
                  # writer functions are roots — span/event entry
                  # points, the per-thread ring accessor, the ring
                  # store, and the span close (the background flusher's
                  # _write_loop is already a root above)
                  "trace_span", "trace_step", "trace_event", "_record",
                  "_ring", "push", "end", "drop"}

# callables whose result is a jitted function / whose first unpacked
# element is one — shared by device-placement and recompile-hazard so a
# new factory registers with both passes at once
JIT_FACTORIES = {"jit", "StaticFunction", "to_static"}
STEP_FACTORIES = {"create_train_step", "create_multistep_train_step",
                  "create_sharded_train_step"}


def assigned_names(node: ast.AST) -> Dict[str, int]:
    """name -> last binding lineno within ``node``. The loop-variance
    test uses the keys as a set; the lagged-fetch allowance compares the
    linenos. Covers Assign/AugAssign/AnnAssign, for-targets, walrus,
    ``with ... as``, and comprehension targets."""
    out: Dict[str, int] = {}

    def bind(t: ast.AST, lineno: int):
        if isinstance(t, ast.Name):
            out[t.id] = max(out.get(t.id, 0), lineno)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                bind(e, lineno)
        elif isinstance(t, ast.Starred):
            bind(t.value, lineno)

    for sub in ast.walk(node):
        if isinstance(sub, ast.Assign):
            for t in sub.targets:
                bind(t, sub.lineno)
        elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
            bind(sub.target, sub.lineno)
        elif isinstance(sub, (ast.For, ast.AsyncFor)):
            bind(sub.target, sub.lineno)
        elif isinstance(sub, ast.NamedExpr):
            bind(sub.target, sub.lineno)
        elif isinstance(sub, ast.comprehension):
            # comprehension/withitem nodes carry no position of their
            # own — use the target's
            bind(sub.target, sub.target.lineno)
        elif isinstance(sub, ast.withitem) and sub.optional_vars:
            bind(sub.optional_vars, sub.optional_vars.lineno)
    return out


_SUBSYSTEM_DIRS = {"paddle_tpu", "tools", "tests"}


def is_bench_module(path: str) -> bool:
    """Repo-ROOT bench*.py files only: a bench-named helper inside a
    subsystem tree (tools/bench_utils.py) is not automatically hot."""
    base = os.path.basename(path)
    if not (base.startswith("bench") and base.endswith(".py")):
        return False
    parts = os.path.normpath(path).replace(os.sep, "/").split("/")[:-1]
    return not (_SUBSYSTEM_DIRS & set(parts))


def is_hot_module(path: str) -> bool:
    norm = path.replace(os.sep, "/")
    return is_bench_module(path) \
        or any(r.search(norm) for r in HOT_MODULE_RES)


def _called_names(fn: ast.AST) -> Set[str]:
    """Names this function calls: ``foo(...)`` and ``self.foo(...)``
    (the intra-module edges we can resolve without type inference)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            out.add(f.id)
        elif isinstance(f, ast.Attribute) \
                and isinstance(f.value, ast.Name) \
                and f.value.id in ("self", "cls"):
            out.add(f.attr)
    return out


def hot_functions(tree: ast.Module, path: str
                  ) -> List[Tuple[ast.AST, str]]:
    """[(fn_node, why_hot)] — every function def in this module that the
    hot-path model marks hot. Empty when the module is not hot."""
    if not is_hot_module(path):
        return []
    defs: List[ast.AST] = [n for n in ast.walk(tree) if isinstance(
        n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    by_name: Dict[str, List[ast.AST]] = {}
    for d in defs:
        by_name.setdefault(d.name, []).append(d)

    bench = is_bench_module(path)
    roots: List[ast.AST] = []
    for d in defs:
        if d.name in HOT_ROOT_NAMES:
            roots.append(d)
    if bench:
        for n in tree.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                roots.append(n)

    hot: Dict[int, Tuple[ast.AST, str]] = {}
    stack: List[Tuple[ast.AST, str]] = [(r, f"hot root {r.name!r}")
                                        for r in roots]
    while stack:
        fn, why = stack.pop()
        if id(fn) in hot:
            continue
        hot[id(fn)] = (fn, why)
        for name in _called_names(fn):
            for callee in by_name.get(name, []):
                if id(callee) not in hot:
                    stack.append(
                        (callee, f"reachable from hot path via {name!r}"))
        # nested defs run as part of the hot function
        for sub in ast.walk(fn):
            if sub is not fn and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and id(sub) not in hot:
                stack.append((sub, f"nested in hot {fn.name!r}"))
    return list(hot.values())
