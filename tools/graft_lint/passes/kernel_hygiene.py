"""Pallas/Mosaic kernel-hygiene pass (GL9xx): tiling legality, grid
coverage, padded-tail numerics, accumulation precision, VMEM budget,
and interpret-mode drift.

The kernel invariants this pass checks are exactly the ones that only
fail on hardware (or at non-multiple-of-block shapes): Mosaic rejects a
rank-1 VMEM block at compile time on a TPU but interpret mode happily
runs it; an unmasked padded-tail reduction is bit-correct on every
block-multiple test shape; a bf16 dot without
``preferred_element_type`` silently loses mantissa. All of them are
checkable properties of the ``pl.pallas_call`` site (see
``_kernelmodel``), so they are checked here, at lint time. Every rule
flags only what the model can PROVE from the AST — unknown dims, specs
built dynamically, or parameter-typed operands are skipped, never
guessed at.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..core import Finding, LintPass, register
from ..fixes import call_keyword_fix
from ._kernelmodel import (DTYPE_BYTES, LANE, LOW_PRECISION, SUBLANE,
                           VMEM_BYTES, BlockSpec, ModuleKernelModel,
                           PallasCall, callee_name, dotted, dtype_name,
                           index_map_arity, index_map_targets,
                           kernel_ref_params)

_REDUCERS = {"sum", "mean", "max", "min", "prod", "amax", "amin"}
_DOTS = {"dot", "dot_general"}


def _fmt_shape(shape) -> str:
    return "(" + ", ".join("?" if d is None else str(d)
                           for d in shape) + ")"


@register
class KernelHygienePass(LintPass):
    """Pallas/Mosaic kernel hygiene: block tiling, grid coverage,
    padded-tail masks, fp32 accumulation, VMEM budget, interpret drift."""

    name = "kernel-hygiene"
    rules = {
        "GL901": "illegal block tiling: rank-1 VMEM block, trailing "
                 "block dim neither a 128-multiple nor the full array "
                 "dim, or second-minor dim not a multiple of the dtype "
                 "sublane (8 f32 / 16 bf16 / 32 int8)",
        "GL902": "grid/index_map coverage mismatch: grid x block under-"
                 " or over-covers the array dim (silent truncation or "
                 "OOB), or index_map arity disagrees with the grid or "
                 "block rank",
        "GL903": "kernel reduces over a padded axis with no "
                 "broadcasted_iota validity mask — wrong results at "
                 "non-multiple-of-block shapes",
        "GL904": "low-precision accumulation: dot/dot_general over raw "
                 "ref values without preferred_element_type (or "
                 "sum/mean of a provably bf16/fp16 value) — accumulate "
                 "in float32",
        "GL905": "estimated VMEM footprint of the blocks (+scratch, "
                 "in/out double-buffered) exceeds ~75% of the call's "
                 "scoped-VMEM limit (16 MiB unless CompilerParams sets "
                 "vmem_limit_bytes)",
        "GL906": "interpret/backend selection computed locally in a "
                 "pallas_call module — route through the shared "
                 "paddle_tpu/ops/pallas/common.py helper "
                 "(pallas_interpret()/on_tpu())",
    }

    def check_module(self, tree: ast.Module, src: str,
                     path: str) -> List[Finding]:
        has_pallas = any(isinstance(n, ast.Call)
                         and callee_name(n) == "pallas_call"
                         for n in ast.walk(tree))
        if not has_pallas:
            return []
        model = ModuleKernelModel(tree, path)
        findings: List[Finding] = []
        seen_kernels: Set[int] = set()
        for pc in model.calls:
            self._check_tiling(pc, model, findings)
            self._check_coverage(pc, model, findings)
            self._check_padded_tail(pc, model, findings)
            self._check_precision(pc, model, src, findings,
                                  seen_kernels)
            self._check_vmem(pc, model, findings)
        self._check_interpret(tree, model, findings)
        findings.sort(key=lambda f: (f.line, f.rule, f.message))
        return findings

    # -- shared spec context -------------------------------------------

    def _site(self, pc: PallasCall) -> str:
        fn = pc.enclosing
        return fn.name if fn is not None else "<module>"

    def _spec_rows(self, pc: PallasCall, model: ModuleKernelModel
                   ) -> List[Tuple[BlockSpec, str, Optional[List],
                                   Optional[str]]]:
        """[(spec, symbol, array_dims, dtype)] for every resolvable in/
        out spec of the call, with the full array dims and element dtype
        when provable (operand provenance for inputs, out_shape structs
        for outputs)."""
        rows = []
        site = self._site(pc)
        in_specs = pc.in_specs or []
        ops_aligned = pc.operands is not None \
            and len(pc.operands) == len(in_specs)
        for i, spec in enumerate(in_specs):
            dims = dtype = None
            if ops_aligned:
                origin = model.operand_origin(pc.operands[i], pc.env)
                dims, dtype = origin.dims, origin.dtype
            rows.append((spec, f"{site}.in_specs[{i}]", dims, dtype))
        out_specs = pc.out_specs or []
        outs_aligned = pc.out_shapes is not None \
            and len(pc.out_shapes) == len(out_specs)
        for i, spec in enumerate(out_specs):
            dims = dtype = None
            if outs_aligned:
                dims = pc.out_shapes[i].shape
                dtype = pc.out_shapes[i].dtype
            rows.append((spec, f"{site}.out_specs[{i}]", dims, dtype))
        return rows

    # -- GL901: tiling legality ----------------------------------------

    def _check_tiling(self, pc: PallasCall, model: ModuleKernelModel,
                      findings: List[Finding]) -> None:
        for spec, symbol, arr_dims, dtype in self._spec_rows(pc, model):
            if spec.memory_space in ("SMEM", "ANY"):
                continue             # scalars/control flow: no lane rule
            shape = spec.shape
            if shape is None:
                continue             # whole-array block
            rank = len(shape)
            line = spec.node.lineno

            def full_dim(axis: int, val) -> bool:
                if arr_dims is None or len(arr_dims) != rank \
                        or val is None:
                    return False
                return arr_dims[axis] == val

            trailing = shape[-1]
            if rank == 1:
                ok = (isinstance(trailing, int)
                      and trailing % LANE == 0) \
                    or full_dim(0, trailing)
                if not ok:
                    findings.append(self._finding(
                        "GL901", pc.path, line,
                        f"rank-1 VMEM block {_fmt_shape(shape)}: Mosaic "
                        "rejects rank-1 blocks whose dim is neither a "
                        "128-multiple nor the full array dim — use a "
                        "(rows, 1) trailing-unit block, or "
                        "memory_space=pltpu.SMEM for scalars",
                        symbol=symbol))
                continue
            if isinstance(trailing, int) and trailing % LANE != 0 \
                    and not full_dim(rank - 1, trailing):
                arr_trailing = arr_dims[-1] if arr_dims \
                    and len(arr_dims) == rank else None
                if trailing != 1 or isinstance(arr_trailing, int):
                    # trailing-unit (rows, 1) scalar blocks are the
                    # blessed idiom — legal exactly when the array's
                    # trailing dim IS 1, so only flag them when the
                    # array dim is known and disagrees
                    findings.append(self._finding(
                        "GL901", pc.path, line,
                        f"trailing block dim {trailing} of "
                        f"{_fmt_shape(shape)} is neither a 128-multiple "
                        "nor the full array dim",
                        symbol=symbol))
            sm = shape[-2]
            if isinstance(sm, int) and sm > 1 \
                    and not full_dim(rank - 2, sm):
                sub = SUBLANE.get(dtype or "", 8)
                if sm % sub != 0:
                    findings.append(self._finding(
                        "GL901", pc.path, line,
                        f"second-minor block dim {sm} of "
                        f"{_fmt_shape(shape)} is not a multiple of the "
                        f"{dtype or 'assumed-f32'} sublane count "
                        f"({sub})",
                        symbol=symbol))

    # -- GL902: grid / index_map coverage ------------------------------

    def _check_coverage(self, pc: PallasCall, model: ModuleKernelModel,
                        findings: List[Finding]) -> None:
        grid = pc.grid
        for spec, symbol, arr_dims, _dtype in self._spec_rows(pc, model):
            imap = spec.index_map
            n_par, n_ret = index_map_arity(imap)
            line = imap.lineno if imap is not None else spec.node.lineno
            if n_par is not None and grid is not None \
                    and n_par != len(grid):
                findings.append(self._finding(
                    "GL902", pc.path, line,
                    f"index_map takes {n_par} grid indices but the "
                    f"grid has {len(grid)} dims",
                    symbol=symbol))
                continue
            if n_ret is not None and spec.shape is not None \
                    and n_ret != len(spec.shape):
                findings.append(self._finding(
                    "GL902", pc.path, line,
                    f"index_map returns {n_ret} block coords for a "
                    f"rank-{len(spec.shape)} block "
                    f"{_fmt_shape(spec.shape)}",
                    symbol=symbol))
                continue
            if grid is None or spec.shape is None:
                continue
            targets = index_map_targets(imap)
            if not targets:
                continue
            for gpos, axis in targets.items():
                if gpos >= len(grid) or axis >= len(spec.shape):
                    continue
                g = model.eval_int(grid[gpos], pc.env)
                b = spec.shape[axis]
                n = arr_dims[axis] if arr_dims is not None \
                    and len(arr_dims) == len(spec.shape) else None
                if not (isinstance(g, int) and isinstance(b, int)
                        and isinstance(n, int)) or b <= 0:
                    continue
                if g * b < n:
                    findings.append(self._finding(
                        "GL902", pc.path, spec.node.lineno,
                        f"grid dim {gpos} ({g} blocks of {b}) covers "
                        f"only {g * b} of {n} elements on array axis "
                        f"{axis} — the tail is silently never computed "
                        "(pad the operand or use pl.cdiv)",
                        symbol=symbol))
                elif (g - 1) * b >= n:
                    findings.append(self._finding(
                        "GL902", pc.path, spec.node.lineno,
                        f"grid dim {gpos} ({g} blocks of {b}) indexes "
                        f"past array axis {axis} (size {n}) — "
                        "out-of-bounds blocks",
                        symbol=symbol))

    # -- GL903: padded-tail reduction without a mask -------------------

    def _check_padded_tail(self, pc: PallasCall,
                           model: ModuleKernelModel,
                           findings: List[Finding]) -> None:
        kernel = pc.kernel
        if kernel is None or pc.in_specs is None \
                or pc.operands is None \
                or len(pc.operands) != len(pc.in_specs):
            return
        params = kernel_ref_params(kernel)
        n_out = len(pc.out_specs) if pc.out_specs is not None else None
        if params is None or n_out is None:
            return
        n_scratch = len(pc.scratch or [])
        if len(params) != len(pc.in_specs) + n_out + n_scratch:
            return
        padded: Dict[str, Set[int]] = {}
        for i, op in enumerate(pc.operands):
            origin = model.operand_origin(op, pc.env)
            axes = {a for a in origin.padded_axes if a >= 0}
            if axes:
                padded[params[i]] = axes
        if not padded:
            return
        if any(isinstance(n, ast.Call)
               and callee_name(n) == "broadcasted_iota"
               for n in ast.walk(kernel)):
            return                    # kernel builds a validity mask
        taints = self._taint_kernel(kernel, padded)
        for call in ast.walk(kernel):
            if not isinstance(call, ast.Call) or not call.args:
                continue
            name = callee_name(call)
            if name not in _REDUCERS:
                continue
            axes = self._expr_axes(call.args[0], taints)
            if not axes:
                continue
            kw = {k.arg: k.value for k in call.keywords if k.arg}
            axis = kw.get("axis",
                          call.args[1] if len(call.args) > 1 else None)
            hit: Optional[int] = None
            if axis is None:
                hit = sorted(axes)[0]          # full reduction
            elif isinstance(axis, ast.Constant) \
                    and isinstance(axis.value, int) \
                    and axis.value >= 0:
                if axis.value in axes:
                    hit = axis.value
            elif isinstance(axis, ast.Tuple):
                for e in axis.elts:
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, int) \
                            and e.value >= 0 and e.value in axes:
                        hit = e.value
                        break
            if hit is None:
                continue
            findings.append(self._finding(
                "GL903", pc.path, call.lineno,
                f"kernel {pc.kernel_name!r}: {name}() reduces over "
                f"axis {hit}, which carries a padded tail "
                "(pad_rows/pad_seq operand), with no broadcasted_iota "
                "validity mask — wrong values at non-multiple-of-block "
                "shapes",
                symbol=f"{pc.kernel_name}.{name}@axis{hit}"))

    def _taint_kernel(self, kernel: ast.AST,
                      padded: Dict[str, Set[int]]
                      ) -> Dict[str, Set[int]]:
        """Forward pass over the kernel's assignments: var -> kernel-
        local axes that carry a padded tail."""
        taints: Dict[str, Set[int]] = dict(padded)

        def visit(body) -> None:
            for stmt in body:
                if isinstance(stmt, ast.Assign) \
                        and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    axes = self._expr_axes(stmt.value, taints)
                    if axes:
                        taints[stmt.targets[0].id] = axes
                    else:
                        taints.pop(stmt.targets[0].id, None)
                for attr in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, attr, None)
                    if sub:
                        visit(sub)

        visit(kernel.body)
        return taints

    def _expr_axes(self, e: ast.expr,
                   taints: Dict[str, Set[int]]) -> Set[int]:
        if isinstance(e, ast.Name):
            return set(taints.get(e.id, ()))
        if isinstance(e, ast.Subscript):
            base = e.value
            if isinstance(base, ast.Name) and base.id in taints:
                axes = taints[base.id]
                sl = e.slice
                elts = list(sl.elts) if isinstance(sl, ast.Tuple) \
                    else [sl]
                shift = 0
                for el in elts:       # ref[0] / ref[0, ...]: axes shift
                    if isinstance(el, ast.Constant) \
                            and isinstance(el.value, int):
                        shift += 1
                    else:
                        break
                return {a - shift for a in axes if a >= shift}
            return self._expr_axes(base, taints)
        if isinstance(e, ast.BinOp):
            return self._expr_axes(e.left, taints) \
                | self._expr_axes(e.right, taints)
        if isinstance(e, ast.UnaryOp):
            return self._expr_axes(e.operand, taints)
        if isinstance(e, ast.Compare):
            out = self._expr_axes(e.left, taints)
            for c in e.comparators:
                out |= self._expr_axes(c, taints)
            return out
        if isinstance(e, ast.Call):
            name = callee_name(e)
            if name in _REDUCERS or name in _DOTS:
                return set()          # reduced result: axes collapsed
            if name == "astype" and isinstance(e.func, ast.Attribute):
                return self._expr_axes(e.func.value, taints)
            out: Set[int] = set()
            for a in e.args:
                out |= self._expr_axes(a, taints)
            return out
        if isinstance(e, ast.Attribute):
            return self._expr_axes(e.value, taints)
        return set()

    # -- GL904: low-precision accumulation -----------------------------

    def _check_precision(self, pc: PallasCall,
                         model: ModuleKernelModel, src: str,
                         findings: List[Finding],
                         seen_kernels: Set[int]) -> None:
        kernel = pc.kernel
        if kernel is None or id(kernel) in seen_kernels:
            return
        seen_kernels.add(id(kernel))
        params = kernel_ref_params(kernel)
        if params is None:
            return
        raw: Set[str] = set(params)   # names holding raw-ref values
        dtypes: Dict[str, str] = {}

        def expr_raw(e: ast.expr) -> bool:
            if isinstance(e, ast.Name):
                return e.id in raw
            if isinstance(e, ast.Subscript):
                return expr_raw(e.value)
            if isinstance(e, ast.BinOp):
                return expr_raw(e.left) or expr_raw(e.right)
            if isinstance(e, ast.UnaryOp):
                return expr_raw(e.operand)
            if isinstance(e, ast.Call):
                name = callee_name(e)
                if name == "astype" and e.args:
                    dt = dtype_name(e.args[0])
                    if dt in ("float32", "float64"):
                        return False
                    if dt in LOW_PRECISION:
                        return True
                    return isinstance(e.func, ast.Attribute) \
                        and expr_raw(e.func.value)
                if name in _DOTS:
                    kw = {k.arg for k in e.keywords}
                    if "preferred_element_type" in kw:
                        return False  # f32 accumulator
                return any(expr_raw(a) for a in e.args)
            if isinstance(e, ast.Attribute):
                return expr_raw(e.value)
            return False

        def expr_dtype(e: ast.expr) -> Optional[str]:
            if isinstance(e, ast.Name):
                return dtypes.get(e.id)
            if isinstance(e, ast.Call):
                name = callee_name(e)
                if name == "astype" and e.args:
                    return dtype_name(e.args[0])
                if name in _DOTS:
                    kw = {k.arg: k.value for k in e.keywords if k.arg}
                    return dtype_name(kw.get("preferred_element_type"))
            if isinstance(e, ast.BinOp):
                l, r = expr_dtype(e.left), expr_dtype(e.right)
                return l if l == r else None
            return None

        def visit(body) -> None:
            for stmt in body:
                if isinstance(stmt, ast.Assign) \
                        and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    name = stmt.targets[0].id
                    if expr_raw(stmt.value):
                        raw.add(name)
                    else:
                        raw.discard(name)
                    dt = expr_dtype(stmt.value)
                    if dt:
                        dtypes[name] = dt
                    else:
                        dtypes.pop(name, None)
                for attr in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, attr, None)
                    if sub:
                        visit(sub)

        visit(kernel.body)

        for call in ast.walk(kernel):
            if not isinstance(call, ast.Call):
                continue
            name = callee_name(call)
            if name in _DOTS:
                kw = {k.arg for k in call.keywords}
                if "preferred_element_type" in kw or not call.args:
                    continue
                if any(expr_raw(a) for a in call.args[:2]):
                    fn = self._finding(
                        "GL904", pc.path, call.lineno,
                        f"kernel {pc.kernel_name!r}: {name}() over raw "
                        "ref values without preferred_element_type — "
                        "the MXU accumulates in the input dtype "
                        "(bf16 inputs lose mantissa); pass "
                        "preferred_element_type=jnp.float32",
                        symbol=f"{pc.kernel_name}.{name}"
                               f"@L{call.lineno}")
                    fn.fix = call_keyword_fix(
                        src, call, "preferred_element_type",
                        "jnp.float32",
                        note="accumulate the dot in float32")
                    findings.append(fn)
            elif name in ("sum", "mean") and call.args:
                kw = {k.arg for k in call.keywords}
                if "dtype" in kw:
                    continue
                dt = expr_dtype(call.args[0])
                if dt in LOW_PRECISION:
                    findings.append(self._finding(
                        "GL904", pc.path, call.lineno,
                        f"kernel {pc.kernel_name!r}: {name}() over a "
                        f"{dt} value accumulates in {dt} — astype to "
                        "float32 (or pass dtype=jnp.float32) before "
                        "reducing",
                        symbol=f"{pc.kernel_name}.{name}"
                               f"@L{call.lineno}"))

    # -- GL905: VMEM footprint -----------------------------------------

    def _check_vmem(self, pc: PallasCall, model: ModuleKernelModel,
                    findings: List[Finding]) -> None:
        total = 0
        for spec, _symbol, arr_dims, dtype in self._spec_rows(pc, model):
            if spec.memory_space == "SMEM":
                continue
            dims = spec.shape if spec.shape is not None else arr_dims
            if dims is None or not all(isinstance(d, int)
                                       for d in dims):
                continue              # unknown blocks: count what we can
            nbytes = DTYPE_BYTES.get(dtype or "", 4)
            for d in dims:
                nbytes *= d
            total += 2 * nbytes       # pipeline double-buffers in/out
        for sc in pc.scratch or []:
            if sc.space == "SMEM" or sc.shape is None \
                    or not all(isinstance(d, int) for d in sc.shape):
                continue
            nbytes = DTYPE_BYTES.get(sc.dtype or "", 4)
            for d in sc.shape:
                nbytes *= d
            total += nbytes
        if pc.vmem_limit is None:
            return          # a limit is set that the model cannot evaluate
        if total > int(pc.vmem_limit * 0.75):
            raised = pc.vmem_limit != VMEM_BYTES
            findings.append(self._finding(
                "GL905", pc.path, pc.line,
                f"estimated VMEM footprint {total / (1 << 20):.1f} MiB "
                "(literal in/out blocks double-buffered + scratch) "
                "exceeds 75% of the "
                + (f"{pc.vmem_limit / (1 << 20):.0f} MiB the call's "
                   "vmem_limit_bytes sets" if raised
                   else "16 MiB/core budget")
                + " — shrink the block tiling",
                symbol=f"{self._site(pc)}.pallas_call"))

    # -- GL906: interpret-mode drift -----------------------------------

    def _check_interpret(self, tree: ast.Module,
                         model: ModuleKernelModel,
                         findings: List[Finding]) -> None:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if not d or not d.endswith("default_backend"):
                continue
            fn = model.enclosing_fn(node)
            site = fn.name if fn is not None else "<module>"
            findings.append(self._finding(
                "GL906", model.path, node.lineno,
                "backend/interpret selection computed locally in a "
                "pallas_call module — every kernel must agree on what "
                "'not on TPU' means; route through "
                "paddle_tpu/ops/pallas/common.py "
                "(pallas_interpret()/on_tpu())",
                symbol=f"{site}.default_backend"))
