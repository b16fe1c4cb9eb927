"""Shared Pallas call-site model for the kernel-hygiene (GL9xx) pass.

``pl.pallas_call`` sites are highly structured — grid, BlockSpecs,
out_shape structs, scratch shapes, and a kernel function whose
positional parameters are the refs those specs feed — and every
invariant the GL9xx rules check (tiling legality, grid coverage,
padded-tail masking, accumulation dtype, VMEM budget) is a property of
that structure. This module resolves the structure from the AST, in the
same intra-module spirit as ``_hotpath``: plain-name function
resolution, single-assignment locals, literal constants. Anything it
cannot prove it reports as unknown (``None`` dims, ``None`` spec
lists), and the pass stays silent there — a kernel-hygiene finding must
be a proof, not a guess.

Resolution the model does:

- ``pl.pallas_call(kernel, ...)`` / bare ``pallas_call`` — kernel
  resolved through the module's def map, including
  ``functools.partial(kernel, **cfg)`` (keyword-only config args are
  not refs; the positional params are).
- ``grid=`` / ``in_specs=`` / ``out_specs=`` / ``out_shape=`` /
  ``scratch_shapes=`` / ``interpret=``, inline or via a
  ``pl.GridSpec(...)``, literal or a single-assignment local name
  (a local later mutated with ``.append``/``.extend`` is unresolvable
  — the dynamically-built flash spec lists stay unknown by design).
- Block shapes / out shapes to per-dim values: int literals, module- or
  function-level int constants, ``np.int32(k)``; everything else keeps
  its symbol name (so "same symbol" reasoning still works) or None.
- Operand provenance in the enclosing function: ``pad_rows(x, br)``
  (pads axis 0 to a multiple of ``br``), ``pad_seq``-style helpers
  (axis 1), ``jnp.pad``, ``.reshape(...)`` literal dims,
  ``jnp.zeros/ones/full/empty`` literal shape+dtype — enough to prove
  "this block dim IS the full array dim" and "this operand carries a
  padded tail".
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

Dim = Union[int, str, None]   # literal | symbol name | unknown

LANE = 128
VMEM_BYTES = 16 * 1024 * 1024

# minimum second-minor (sublane) multiple per dtype — the Mosaic tile
# table: (8, 128) f32, (16, 128) bf16, (32, 128) int8/fp8
SUBLANE = {"float32": 8, "float64": 8, "int32": 8, "uint32": 8,
           "bfloat16": 16, "float16": 16, "int16": 16, "uint16": 16,
           "int8": 32, "uint8": 32,
           "float8_e4m3fn": 32, "float8_e5m2": 32}
DTYPE_BYTES = {"float32": 4, "int32": 4, "uint32": 4,
               "float64": 8, "int64": 8, "uint64": 8,
               "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
               "int8": 1, "uint8": 1, "bool_": 1,
               "float8_e4m3fn": 1, "float8_e5m2": 1}
LOW_PRECISION = {"bfloat16", "float16"}

PAD_ROWS_NAMES = {"pad_rows"}          # pads axis 0
PAD_SEQ_NAMES = {"pad_seq", "_pad_seq"}  # pads axis 1


def dotted(node: ast.AST) -> Optional[str]:
    """'jnp.float32' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def callee_name(call: ast.Call) -> Optional[str]:
    """Last component of the callee name ('pallas_call', 'BlockSpec',
    'astype' for a method call on any expression), or None for
    computed callees."""
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    if isinstance(call.func, ast.Name):
        return call.func.id
    return None


def dtype_name(node: Optional[ast.AST]) -> Optional[str]:
    """'float32' from ``jnp.float32`` / ``np.float32`` / '"float32"'."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in DTYPE_BYTES else None
    d = dotted(node)
    if d:
        tail = d.rsplit(".", 1)[-1]
        if tail in DTYPE_BYTES:
            return tail
    return None


@dataclass
class BlockSpec:
    node: ast.Call
    shape: Optional[List[Dim]] = None     # None: no block_shape given
    index_map: Optional[ast.expr] = None  # usually a Lambda
    memory_space: Optional[str] = None    # "SMEM" / "VMEM" / "ANY"


@dataclass
class OutShape:
    node: ast.AST
    shape: Optional[List[Dim]] = None
    dtype: Optional[str] = None


@dataclass
class Scratch:
    node: ast.AST
    shape: Optional[List[Dim]] = None
    dtype: Optional[str] = None
    space: Optional[str] = None           # "VMEM" / "SMEM" / ...


@dataclass
class Origin:
    """What we can prove about an operand expression."""
    dims: Optional[List[Dim]] = None      # full array dims when known
    dtype: Optional[str] = None
    padded_axes: Dict[int, Dim] = field(default_factory=dict)
    # axis -> block multiple it was padded to (pad_rows/pad_seq)


@dataclass
class PallasCall:
    node: ast.Call                        # the pl.pallas_call(...) call
    path: str
    kernel_name: str = ""
    kernel: Optional[ast.AST] = None      # FunctionDef when resolved
    grid: Optional[List[ast.expr]] = None
    in_specs: Optional[List[BlockSpec]] = None
    out_specs: Optional[List[BlockSpec]] = None
    out_shapes: Optional[List[OutShape]] = None
    scratch: Optional[List[Scratch]] = None
    interpret: Optional[ast.expr] = None
    # scoped-VMEM limit of the call: Mosaic's default, the int a
    # ``CompilerParams(vmem_limit_bytes=...)`` provably sets, or None where
    # a limit is set that cannot be evaluated
    vmem_limit: Optional[int] = VMEM_BYTES
    operands: Optional[List[ast.expr]] = None   # args of the outer call
    enclosing: Optional[ast.AST] = None   # enclosing FunctionDef
    env: Dict[str, ast.expr] = field(default_factory=dict)

    @property
    def line(self) -> int:
        return self.node.lineno


class ModuleKernelModel:
    """All pallas_call sites of one module, with resolution context."""

    def __init__(self, tree: ast.Module, path: str):
        self.tree = tree
        self.path = path
        self.parents: Dict[int, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[id(child)] = parent
        self.defs: Dict[str, ast.AST] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, node)
        self.consts: Dict[str, int] = self._int_consts(tree.body)
        self.calls: List[PallasCall] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and callee_name(node) == "pallas_call":
                self.calls.append(self._build(node))

    # -- construction --------------------------------------------------

    @staticmethod
    def _int_consts(body: Sequence[ast.stmt]) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for stmt in body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and isinstance(stmt.value, ast.Constant) \
                    and isinstance(stmt.value.value, int) \
                    and not isinstance(stmt.value.value, bool):
                out[stmt.targets[0].id] = stmt.value.value
        return out

    def enclosing_fn(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parents.get(id(node))
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(id(cur))
        return None

    def _env(self, fn: Optional[ast.AST]) -> Dict[str, ast.expr]:
        """Single-assignment locals of ``fn``: name -> value expr.
        Multiply-assigned or ``.append``/``.extend``-mutated names are
        dropped — their value at the call site is not this expr."""
        if fn is None:
            return {}
        env: Dict[str, ast.expr] = {}
        dead: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                targets = node.targets
                if len(targets) == 1 and isinstance(targets[0], ast.Name):
                    name = targets[0].id
                    if name in env or name in dead:
                        dead.add(name)
                        env.pop(name, None)
                    else:
                        env[name] = node.value
                else:
                    for t in targets:
                        for sub in ast.walk(t):
                            if isinstance(sub, ast.Name):
                                dead.add(sub.id)
                                env.pop(sub.id, None)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                                   ast.For, ast.AsyncFor)):
                t = node.target
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        dead.add(sub.id)
                        env.pop(sub.id, None)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("append", "extend", "insert",
                                           "pop", "remove") \
                    and isinstance(node.func.value, ast.Name):
                dead.add(node.func.value.id)
                env.pop(node.func.value.id, None)
        return env

    def _build(self, call: ast.Call) -> PallasCall:
        pc = PallasCall(node=call, path=self.path)
        pc.enclosing = self.enclosing_fn(call)
        env = pc.env = self._env(pc.enclosing)
        kw = {k.arg: k.value for k in call.keywords if k.arg}

        # kernel: first positional, through partial and the def map
        if call.args:
            pc.kernel_name, pc.kernel = self._resolve_kernel(call.args[0])

        grid_src: Dict[str, ast.expr] = dict(kw)
        gs = kw.get("grid_spec")
        if gs is not None:
            gs = self._deref(gs, env)
            if isinstance(gs, ast.Call) and callee_name(gs) in (
                    "GridSpec", "PrefetchScalarGridSpec"):
                for k in gs.keywords:
                    if k.arg:
                        grid_src.setdefault(k.arg, k.value)

        grid = self._deref(grid_src.get("grid"), env)
        if isinstance(grid, (ast.Tuple, ast.List)):
            pc.grid = list(grid.elts)
        elif grid is not None and not isinstance(grid, ast.Constant):
            pc.grid = None
        elif isinstance(grid, ast.Constant):
            pc.grid = [grid]

        pc.in_specs = self._spec_list(grid_src.get("in_specs"), env)
        pc.out_specs = self._spec_list(grid_src.get("out_specs"), env)
        pc.out_shapes = self._out_shapes(kw.get("out_shape"), env)
        pc.scratch = self._scratch(kw.get("scratch_shapes"), env)
        pc.interpret = kw.get("interpret")
        pc.vmem_limit = self._vmem_limit(kw.get("compiler_params"), env)

        outer = self.parents.get(id(call))
        if isinstance(outer, ast.Call) and outer.func is call:
            pc.operands = list(outer.args)
        return pc

    def _vmem_limit(self, expr: Optional[ast.expr],
                    env: Dict[str, ast.expr]) -> Optional[int]:
        """The call's scoped-VMEM limit (see ``PallasCall.vmem_limit``).
        ``compiler_params=`` is read inline, through a local name, or
        through a module-level helper that returns a ``CompilerParams``."""
        expr = self._deref(expr, env)
        if expr is None:
            return VMEM_BYTES
        if not isinstance(expr, ast.Call):
            return None
        if callee_name(expr) != "CompilerParams":
            helper = self.defs.get(callee_name(expr) or "")
            built = [r.value for r in ast.walk(helper)
                     if isinstance(r, ast.Return)
                     and isinstance(r.value, ast.Call)
                     and callee_name(r.value) == "CompilerParams"] \
                if helper is not None else []
            if len(built) != 1:
                return None
            expr, env = built[0], self._env(helper)
        limit = {k.arg: k.value for k in expr.keywords}.get(
            "vmem_limit_bytes")
        if limit is None or (isinstance(limit, ast.Constant)
                             and limit.value is None):
            return VMEM_BYTES
        return self.eval_int(limit, env)

    def _resolve_kernel(self, expr: ast.expr
                        ) -> Tuple[str, Optional[ast.AST]]:
        if isinstance(expr, ast.Call) and callee_name(expr) == "partial" \
                and expr.args:
            expr = expr.args[0]
        d = dotted(expr)
        if d is None:
            return "", None
        name = d.rsplit(".", 1)[-1]
        return name, self.defs.get(name)

    def _deref(self, expr: Optional[ast.expr],
               env: Dict[str, ast.expr]) -> Optional[ast.expr]:
        seen = 0
        while isinstance(expr, ast.Name) and expr.id in env and seen < 8:
            expr = env[expr.id]
            seen += 1
        return expr

    def _spec_list(self, expr: Optional[ast.expr],
                   env: Dict[str, ast.expr]
                   ) -> Optional[List[BlockSpec]]:
        expr = self._deref(expr, env)
        if expr is None:
            return None
        if isinstance(expr, (ast.Tuple, ast.List)):
            elts = expr.elts
        else:
            elts = [expr]            # single out_specs
        out: List[BlockSpec] = []
        for e in elts:
            e = self._deref(e, env)
            if not (isinstance(e, ast.Call)
                    and callee_name(e) == "BlockSpec"):
                return None          # one opaque spec poisons the list
            out.append(self._block_spec(e, env))
        return out

    def _block_spec(self, call: ast.Call,
                    env: Dict[str, ast.expr]) -> BlockSpec:
        spec = BlockSpec(node=call)
        args = list(call.args)
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        shape_expr = kw.get("block_shape", args[0] if args else None)
        imap = kw.get("index_map", args[1] if len(args) > 1 else None)
        spec.index_map = self._deref(imap, env)
        ms = kw.get("memory_space")
        if ms is not None:
            d = dotted(ms) or ""
            spec.memory_space = d.rsplit(".", 1)[-1] or None
        shape_expr = self._deref(shape_expr, env)
        if isinstance(shape_expr, (ast.Tuple, ast.List)):
            spec.shape = [self.resolve_dim(d, env)
                          for d in shape_expr.elts]
        return spec

    def _out_shapes(self, expr: Optional[ast.expr],
                    env: Dict[str, ast.expr]
                    ) -> Optional[List[OutShape]]:
        expr = self._deref(expr, env)
        if expr is None:
            return None
        elts = expr.elts if isinstance(expr, (ast.Tuple, ast.List)) \
            else [expr]
        out: List[OutShape] = []
        for e in elts:
            e = self._deref(e, env)
            os_ = OutShape(node=e if e is not None else expr)
            if isinstance(e, ast.Call) \
                    and callee_name(e) == "ShapeDtypeStruct":
                kw = {k.arg: k.value for k in e.keywords if k.arg}
                shp = kw.get("shape", e.args[0] if e.args else None)
                dt = kw.get("dtype",
                            e.args[1] if len(e.args) > 1 else None)
                shp = self._deref(shp, env)
                if isinstance(shp, (ast.Tuple, ast.List)):
                    os_.shape = [self.resolve_dim(d, env)
                                 for d in shp.elts]
                os_.dtype = dtype_name(dt)
            out.append(os_)
        return out

    def _scratch(self, expr: Optional[ast.expr],
                 env: Dict[str, ast.expr]) -> Optional[List[Scratch]]:
        expr = self._deref(expr, env)
        if not isinstance(expr, (ast.Tuple, ast.List)):
            return None
        out: List[Scratch] = []
        for e in expr.elts:
            e = self._deref(e, env)
            sc = Scratch(node=e if e is not None else expr)
            if isinstance(e, ast.Call):
                sc.space = callee_name(e)     # VMEM((...), dtype) / SMEM
                shp = e.args[0] if e.args else None
                shp = self._deref(shp, env)
                if isinstance(shp, (ast.Tuple, ast.List)):
                    sc.shape = [self.resolve_dim(d, env)
                                for d in shp.elts]
                if len(e.args) > 1:
                    sc.dtype = dtype_name(e.args[1])
            out.append(sc)
        return out

    # -- value resolution ----------------------------------------------

    def resolve_dim(self, expr: Optional[ast.expr],
                    env: Dict[str, ast.expr]) -> Dim:
        """One block/array dim -> int literal, symbol name, or None."""
        if expr is None:
            return None
        if isinstance(expr, ast.Constant):
            return expr.value if isinstance(expr.value, int) \
                and not isinstance(expr.value, bool) else None
        if isinstance(expr, ast.UnaryOp) \
                and isinstance(expr.op, ast.USub) \
                and isinstance(expr.operand, ast.Constant) \
                and isinstance(expr.operand.value, int):
            return -expr.operand.value
        if isinstance(expr, ast.Call) and callee_name(expr) in (
                "int32", "int64", "int") and expr.args:
            return self.resolve_dim(expr.args[0], env)
        if isinstance(expr, ast.Name):
            if expr.id in self.consts:
                return self.consts[expr.id]
            val = env.get(expr.id)
            if isinstance(val, ast.Constant) \
                    and isinstance(val.value, int) \
                    and not isinstance(val.value, bool):
                return val.value
            return expr.id            # symbolic
        return None

    def eval_int(self, expr: Optional[ast.expr],
                 env: Dict[str, ast.expr], depth: int = 0
                 ) -> Optional[int]:
        """Integer value of ``expr`` when provable: literals, int
        constants, ``name.shape[i]`` of an operand with known dims,
        and +,-,*,// over those."""
        if expr is None or depth > 12:
            return None
        d = self.resolve_dim(expr, env)
        if isinstance(d, int):
            return d
        if isinstance(expr, ast.Name) and expr.id in env:
            return self.eval_int(env[expr.id], env, depth + 1)
        if isinstance(expr, ast.BinOp):
            a = self.eval_int(expr.left, env, depth + 1)
            b = self.eval_int(expr.right, env, depth + 1)
            if a is None or b is None:
                return None
            if isinstance(expr.op, ast.Add):
                return a + b
            if isinstance(expr.op, ast.Sub):
                return a - b
            if isinstance(expr.op, ast.Mult):
                return a * b
            if isinstance(expr.op, ast.FloorDiv) and b != 0:
                return a // b
            if isinstance(expr.op, ast.Mod) and b != 0:
                return a % b
            return None
        if isinstance(expr, ast.Subscript):
            # name.shape[i]
            base = expr.value
            if isinstance(base, ast.Attribute) and base.attr == "shape":
                origin = self.operand_origin(base.value, env)
                idx = self.resolve_dim(expr.slice, env)
                if origin.dims is not None and isinstance(idx, int):
                    try:
                        dim = origin.dims[idx]
                    except IndexError:
                        return None
                    return dim if isinstance(dim, int) else None
        if isinstance(expr, ast.Call) and callee_name(expr) in (
                "cdiv", "ceil_div"):
            if len(expr.args) == 2:
                a = self.eval_int(expr.args[0], env, depth + 1)
                b = self.eval_int(expr.args[1], env, depth + 1)
                if a is not None and b:
                    return -(-a // b)
        return None

    def operand_origin(self, expr: Optional[ast.expr],
                       env: Dict[str, ast.expr], depth: int = 0
                       ) -> Origin:
        """Provenance of an operand expression (see class docstring)."""
        o = Origin()
        if expr is None or depth > 12:
            return o
        if isinstance(expr, ast.Name):
            if expr.id in env:
                return self.operand_origin(env[expr.id], env, depth + 1)
            return o
        if not isinstance(expr, ast.Call):
            return o
        name = callee_name(expr)
        if name in PAD_ROWS_NAMES and expr.args:
            base = self.operand_origin(expr.args[0], env, depth + 1)
            mult = self.resolve_dim(expr.args[1], env) \
                if len(expr.args) > 1 else None
            base.padded_axes = dict(base.padded_axes)
            base.padded_axes[0] = mult
            if base.dims:
                base.dims = [None] + list(base.dims[1:])
            return base
        if name in PAD_SEQ_NAMES and expr.args:
            base = self.operand_origin(expr.args[0], env, depth + 1)
            mult = self.resolve_dim(expr.args[1], env) \
                if len(expr.args) > 1 else None
            base.padded_axes = dict(base.padded_axes)
            base.padded_axes[1] = mult
            if base.dims and len(base.dims) > 1:
                base.dims = [base.dims[0], None] + list(base.dims[2:])
            return base
        if name == "pad":                     # jnp.pad(x, cfg)
            base = self.operand_origin(expr.args[0], env, depth + 1) \
                if expr.args else Origin()
            base.padded_axes = dict(base.padded_axes)
            base.padded_axes[-1] = None       # somewhere, unknown axis
            base.dims = None
            return base
        if name == "reshape":
            # x.reshape(a, b) / x.reshape((a, b)) / jnp.reshape(x, (..))
            if isinstance(expr.func, ast.Attribute):
                base = self.operand_origin(expr.func.value, env,
                                           depth + 1)
                dim_args = list(expr.args)
            else:
                base = self.operand_origin(
                    expr.args[0], env, depth + 1) if expr.args \
                    else Origin()
                dim_args = list(expr.args[1:])
            if len(dim_args) == 1 and isinstance(
                    dim_args[0], (ast.Tuple, ast.List)):
                dim_args = list(dim_args[0].elts)
            o = Origin(dtype=base.dtype)
            o.dims = [self.resolve_dim(d, env) for d in dim_args] \
                if dim_args else None
            return o
        if name in ("zeros", "ones", "full", "empty") and expr.args:
            shp = self._deref(expr.args[0], env)
            if isinstance(shp, (ast.Tuple, ast.List)):
                o.dims = [self.resolve_dim(d, env) for d in shp.elts]
            dt = None
            kw = {k.arg: k.value for k in expr.keywords if k.arg}
            if "dtype" in kw:
                dt = kw["dtype"]
            elif name == "full" and len(expr.args) > 2:
                dt = expr.args[2]
            elif name != "full" and len(expr.args) > 1:
                dt = expr.args[1]
            o.dtype = dtype_name(dt)
            return o
        if name == "astype" and isinstance(expr.func, ast.Attribute):
            base = self.operand_origin(expr.func.value, env, depth + 1)
            base.dtype = dtype_name(expr.args[0]) if expr.args \
                else base.dtype
            return base
        return o


def index_map_targets(imap: Optional[ast.expr]
                      ) -> Optional[Dict[int, int]]:
    """For a Lambda index map: {grid-arg position -> block axis it
    drives}, from returned bare-Name elements. None when the map is not
    a lambda or does something we cannot follow."""
    if not isinstance(imap, ast.Lambda):
        return None
    argnames = [a.arg for a in imap.args.args]
    body = imap.body
    elts = body.elts if isinstance(body, (ast.Tuple, ast.List)) \
        else [body]
    out: Dict[int, int] = {}
    for axis, e in enumerate(elts):
        if isinstance(e, ast.Name) and e.id in argnames:
            out[argnames.index(e.id)] = axis
    return out


def index_map_arity(imap: Optional[ast.expr]
                    ) -> Tuple[Optional[int], Optional[int]]:
    """(n_params, n_returned) for a Lambda index map, None/None
    otherwise. n_returned is None for non-tuple bodies we can't count
    (a call, a conditional)."""
    if not isinstance(imap, ast.Lambda):
        return None, None
    n_params = len(imap.args.args)
    body = imap.body
    if isinstance(body, (ast.Tuple, ast.List)):
        return n_params, len(body.elts)
    if isinstance(body, (ast.Name, ast.Constant, ast.BinOp,
                         ast.Subscript, ast.Attribute)):
        return n_params, 1
    return n_params, None


def kernel_ref_params(fn: ast.AST) -> Optional[List[str]]:
    """Positional parameter names of a kernel def — the refs. None when
    the signature defeats positional mapping (*args)."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    if fn.args.vararg is not None:
        return None
    names = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    # positional params with defaults are still refs at pallas_call time
    return names
