"""wgrad_fusion_probe.py -- where a large matrix's weight gradient ends.

    chiprun -- python3 tools/wgrad_fusion_probe.py --shape mistral,minicpm
    python3 tools/wgrad_fusion_probe.py --shape mistral      # here: no chip

One block ``RMSNorm -> gate/up -> SwiGLU -> down -> residual`` over ``T``
tokens with bf16 weights, float32 moments and AdamW over its four leaves,
the parameters and moments donated: the part of a decoder's step whose
weight gradients XLA fuses with their AdamW update
(``subtract_convert_fusion.N`` in the cells' traces; PERF.md 7 (F)). Three
forms of the same step:

  a  the update reads the gradient as ``jax.grad`` hands it over (what
     ``Optimizer.apply_gradients`` did until PR 35);
  b  each matrix's gradient passes a ``jax.lax.optimization_barrier`` of its
     own before its update (what ``apply_gradients`` does since);
  c  b, and SwiGLU's backward is materialised once for both ``gate_proj``'s
     and ``up_proj``'s gradients: a barrier on the cotangents of the two
     products' outputs.

On a chip each form runs ``--reps`` steps under ``jax.profiler`` and the
device time of every operation is printed (a step's mean over those
traced), with what the compiled text says the operation holds. Without one each form is
compiled for a described v5e (no chip, no times) and every fusion that
holds a convolution is printed with what else it holds and the compiler's
``estimated_cycles``: no clock, but it shows where the fusion's boundary
lies. The rows go to ``chiprun_out/wgrad_fusion_probe.json`` too. Touches
nothing a cell runs.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import re
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (tokens a step, hidden, intermediate) of the cell's dense MLP
SHAPES = {
    "mistral": (16384, 4096, 14336),       # mistral7b-l2-train-s4096
    "minicpm": (12288, 4096, 16384),       # minicpm-sala-train-s12288
    "laguna_dense": (16384, 2048, 8192),   # laguna-xs2-train-s8192, layer 0
    "gpt2": (32768, 768, 3072),            # gpt2s-train-s1024 (its MLP's sizes)
    "tiny": (256, 128, 256),               # for a look on the CPU
}
VARIANTS = ("a", "b", "c")
EPS, LR = 1e-5, 3e-4
# what a fused computation is said to hold: these opcodes, if it has them
_SHOWN = ("convolution", "sqrt", "divide", "multiply", "add", "subtract",
          "exponential", "logistic", "reduce", "convert", "transpose",
          "copy")


def build(shape, variant):
    """(step, abstract args): ``step(params, moments, x, r)`` -> (loss,
    params, moments); the loss is ``sum(block(x) * r)``, so the block's
    output gets the cotangent ``r`` a next layer would send."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.optimizer import AdamW

    t, h, f = SHAPES[shape]
    opt = AdamW(learning_rate=LR, weight_decay=0.01)

    @jax.custom_vjp
    def once(g, u):
        return g, u

    def once_bwd(_, cts):
        return jax.lax.optimization_barrier(cts)
    once.defvjp(lambda g, u: ((g, u), None), once_bwd)

    def block(p, x):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        n = (x32 * jax.lax.rsqrt(var + EPS)
             * p["norm"].astype(jnp.float32)).astype(x.dtype)
        g, u = n @ p["gate"], n @ p["up"]
        if variant == "c":
            g, u = once(g, u)
        return x + (jax.nn.silu(g) * u) @ p["down"]

    def loss(p, x, r):
        return jnp.sum(block(p, x).astype(jnp.float32)
                       * r.astype(jnp.float32))

    def step(params, moments, x, r):
        value, grads = jax.value_and_grad(loss)(params, x, r)
        new_p, new_m = {}, {}
        for k, p in params.items():
            g = grads[k]
            if variant != "a" and g.ndim >= 2:
                g = jax.lax.optimization_barrier(g)
            q, new_m[k] = opt._update(
                p.astype(jnp.float32), g.astype(jnp.float32), moments[k],
                LR, wd=0.01 if g.ndim >= 2 else 0.0)
            new_p[k] = q.astype(p.dtype)
        return value, new_p, new_m

    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    params = {"norm": bf16((h,)), "gate": bf16((h, f)), "up": bf16((h, f)),
              "down": bf16((f, h))}
    moments = jax.eval_shape(opt.init_state_tree, params)
    step.__name__ = f"wgrad_{shape}_{variant}"
    return (jax.jit(step, donate_argnums=(0, 1)),
            (params, moments, bf16((t, h)), bf16((t, h))))


def fusions(text):
    """{instruction name: {"holds": {opcode: count}, "cycles": int or None,
    "out": result type}} for every fusion a compiled program's text runs as
    one operation, what it holds counted through the fusions nested in it,
    and the same for a convolution the compiler left on its own."""
    comps, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and "->" in line and line[:1] not in " \t":
            name = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", line).group(1)
            comps[name] = []
            continue
        inst, eq, rhs = line.strip().partition(" = ")
        op = re.search(r"\s([a-z][\w\-]*)\(", rhs) if eq and name else None
        if op:
            comps[name].append((inst.removeprefix("ROOT ").lstrip("%"),
                                rhs[:op.start()], op.group(1),
                                rhs[op.end():]))

    def callee(rest):
        m = re.search(r"calls=%?([\w.\-]+)", rest)
        return m.group(1) if m else None

    @functools.lru_cache(maxsize=None)
    def holds(comp):
        total = collections.Counter()
        for _, _, opcode, rest in comps.get(comp, ()):
            if opcode == "fusion":
                total += holds(callee(rest))
            else:
                total[opcode] += 1
        return total

    bodies = {callee(rest) for rows in comps.values()
              for _, _, opcode, rest in rows if opcode == "fusion"}
    out = {}
    for comp, rows in comps.items():
        if comp in bodies:
            continue
        for inst, result, opcode, rest in rows:
            if opcode not in ("fusion", "convolution"):
                continue
            held = (holds(callee(rest)) if opcode == "fusion"
                    else collections.Counter(convolution=1))
            cycles = re.search(r'"estimated_cycles":"?(\d+)', rest)
            out[inst] = {"holds": {k: held[k] for k in _SHOWN if held[k]},
                         "cycles": int(cycles.group(1)) if cycles else None,
                         "out": re.sub(r"\{[^}]*\}", "", result)}
    return out


def _is_weight(result, h, f):
    """Whether an operation's first result has a weight matrix's shape."""
    m = re.search(r"\[(\d+),(\d+)\]", result)
    return bool(m) and {int(m.group(1)), int(m.group(2))} == {h, f}


def _kinds(ops, names=None):
    """(operations that hold a convolution, operations that hold AdamW's
    ``sqrt`` and none), of ``names`` or of all."""
    names = ops if names is None else [k for k in names if k in ops]
    conv = {k for k in names if ops[k]["holds"].get("convolution")}
    upd = {k for k in names
           if ops[k]["holds"].get("sqrt") and k not in conv}
    return conv, upd


def _held(holds):
    return " ".join(f"{k}:{n}" for k, n in holds.items()) or "-"


def described_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def compile_only(shapes, variants):
    import jax
    chip = described_chip()
    rows = []
    for shape in shapes:
        for variant in variants:
            step, args = build(shape, variant)
            args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=chip), args)
            compiled = step.lower(*args).compile()
            mem = compiled.memory_analysis()
            ops = fusions(compiled.as_text())
            conv, upd = _kinds(ops)
            row = {"shape": shape, "variant": variant,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "conv_cycles": sum(ops[k]["cycles"] or 0 for k in conv),
                   "update_cycles": sum(ops[k]["cycles"] or 0 for k in upd),
                   "ops": {k: v for k, v in ops.items()
                           if k in conv or k in upd}}
            rows.append(row)
            print(f"\n{shape} ({'x'.join(map(str, SHAPES[shape]))}) form "
                  f"{variant}: temporaries {mem.temp_size_in_bytes / 1e9:.3f}"
                  f" GB; estimated cycles: with a convolution "
                  f"{row['conv_cycles'] / 1e6:.1f} M, update alone "
                  f"{row['update_cycles'] / 1e6:.1f} M")
            for k, v in row["ops"].items():
                print(f"  {k:<34}{(v['cycles'] or 0) / 1e6:8.2f} M  "
                      f"{v['out']:<22} {_held(v['holds'])}", flush=True)
    return rows


def measure(shapes, variants, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import trace as _trace

    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()!r}")
    rows = []
    for shape in shapes:
        t, h, f = SHAPES[shape]
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.standard_normal((t, h)), jnp.bfloat16)
        r = jnp.asarray(rng.standard_normal((t, h)) * 1e-3, jnp.bfloat16)
        for variant in variants:
            step, (params, moments, _, _) = build(shape, variant)
            key = jax.random.key(0)
            p = {k: (jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                       jnp.float32) * 0.02).astype(s.dtype)
                 for i, (k, s) in enumerate(params.items())}
            p["norm"] = jnp.ones_like(p["norm"])
            m = jax.tree.map(lambda s: jnp.ones(s.shape, s.dtype)
                             if s.shape == () else
                             jnp.zeros(s.shape, s.dtype), moments)
            compiled = step.lower(p, m, x, r).compile()
            ops = fusions(compiled.as_text())
            mem = compiled.memory_analysis()
            for _ in range(2):
                loss, p, m = compiled(p, m, x, r)
            jax.block_until_ready(loss)
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(reps):
                    loss, p, m = compiled(p, m, x, r)
                jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                events = _trace.load_events(_trace.find_xplane(tmp))
            del p, m
            per_op, module = collections.defaultdict(list), []
            for key_, evs in events.items():
                if key_.endswith("|" + _trace.MODULE_LINE):
                    module += [d / 1e6 for n, _, d in evs
                               if step.__name__ in n]
                elif key_.endswith("|" + _trace.OP_LINE):
                    for n, _, d in evs:
                        per_op[n.partition(" = ")[0].lstrip("%")].append(
                            d / 1e6)
            # an operation runs once a step: its time is the sum of its
            # events over the steps traced
            op_ms = {k: sum(v) / max(len(module), 1)
                     for k, v in per_op.items()}
            conv, upd = _kinds(ops, op_ms)
            wgrad = {k for k in conv if _is_weight(ops[k]["out"], h, f)}
            row = {"shape": shape, "variant": variant, "steps": len(module),
                   "step_ms": statistics.median(module) if module else None,
                   "wgrad_ms": sum(op_ms[k] for k in wgrad),
                   "other_conv_ms": sum(op_ms[k] for k in conv - wgrad),
                   "update_ms": sum(op_ms[k] for k in upd),
                   "temp_bytes": mem.temp_size_in_bytes,
                   "ops": {k: {"ms": ms, **ops.get(k, {})}
                           for k, ms in sorted(op_ms.items(),
                                               key=lambda kv: -kv[1])
                           if ms >= 0.05}}
            rows.append(row)
            print(f"\n{shape} ({t}x{h}x{f}) form {variant}: step "
                  f"{row['step_ms']:.3f} ms over {len(module)} steps; weight "
                  f"gradients {row['wgrad_ms']:.3f}, other products "
                  f"{row['other_conv_ms']:.3f}, updates alone "
                  f"{row['update_ms']:.3f}; temporaries "
                  f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
            for k, v in row["ops"].items():
                if v["ms"] < 0.2:
                    continue
                print(f"  {k:<34}{v['ms']:8.3f} ms  "
                      f"{v.get('out', ''):<22} {_held(v.get('holds', {}))}",
                      flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", default="mistral,minicpm",
                    help="of " + ",".join(SHAPES))
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/wgrad_fusion_probe.json")
    a = ap.parse_args(argv)
    shapes, variants = a.shape.split(","), a.variants.split(",")
    import jax
    on_chip = jax.default_backend() == "tpu"
    rows = (measure(shapes, variants, a.reps) if on_chip
            else compile_only(shapes, variants))
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as fh:
        json.dump({"on_chip": on_chip, "rows": rows}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
