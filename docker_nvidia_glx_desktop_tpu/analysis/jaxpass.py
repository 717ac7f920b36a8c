"""jax-pass: retrace / host-sync lints over the device program.

Scope: ``ops/``, ``models/``, ``parallel/`` — everywhere a traced value
can leak to the host or a trace can silently re-specialize.  Rules:

- ``jax-host-sync`` — ``float()/int()/bool()``, ``.item()/.tolist()``
  or ``np.asarray/np.array`` applied to a *traced* value inside a
  jit-compiled function (or a ``lax.scan``/``fori_loop``/``while_loop``
  body).  Inside a trace these either abort with a tracer error or —
  the silent case this lint exists for — concretize at trace time and
  bake a stale constant into the executable.  In eager hot paths the
  same call is a synchronous device round-trip per frame.
- ``jax-host-roundtrip`` — a value pulled to the host with
  ``np.asarray`` and then re-uploaded (``jnp.asarray``/``jnp.array``/
  ``device_put``) in the same hot-path function: two wire crossings
  (a full round trip each) for work the device
  could do in place.
- ``jax-donate-missing`` — a jitted function takes ring-buffer-style
  arguments (``ref_*``/``prev_*``/``carry``/``ring*``) but declares no
  ``donate_argnums``/``donate_argnames``: every step copies the ring
  instead of aliasing it (ROADMAP item 2's donated-buffer step).
- ``jax-nonhashable-static`` — a ``static_argnames`` entry whose
  parameter default is unhashable (list/dict/set): every call raises
  once that default is exercised.
- ``jax-unmarked-static`` — a ``str``/``bool``-annotated parameter of a
  jitted function that is not marked static: strings fail at trace
  time; bools trace into the graph and turn Python branching into a
  TracerBoolConversionError (or a retrace per value when hashed).
- ``jax-float64`` — explicit float64 (``astype``/``dtype=``/
  ``np.float64()``) inside a jitted function: under the default x64
  switch this silently becomes float32; with x64 enabled it doubles
  device memory traffic.  Either way the kernel author meant one of
  them, so say which (dngd pragma the deliberate case).
- ``jax-mutable-global-capture`` — a module-level ``list``/``dict``/
  ``set`` read inside a jitted function: the trace captures a snapshot,
  later mutations never re-trigger tracing, and the executable serves
  stale data forever.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from .engine import JAX_SCOPE, Finding, SourceFile, register_pass

__all__ = ["run"]

# method/function name prefixes that constitute the per-frame hot path
# for the eager-context round-trip rule (models orchestration code)
HOT_PATH_PREFIXES = ("encode", "_encode", "_submit", "_collect", "_pull",
                     "_gop_step", "_planes", "step", "_step")

_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist"}
_UNTAINT_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes"}
_RING_NAMES = {"carry", "ring"}
_RING_PREFIXES = ("ref_", "prev_", "ring_")
_LAX_BODY_FNS = {"scan", "fori_loop", "while_loop", "cond", "switch",
                 "associative_scan"}


def _dotted(node: ast.AST) -> str:
    """'jnp.asarray' for Attribute chains, 'float' for Names, else ''."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


class JitSpec:
    """What a jit wrapper declares about a function."""

    def __init__(self):
        self.is_jit = False
        self.static_names: Set[str] = set()
        self.static_nums: Set[int] = set()
        self.donates = False

    def absorb_call_kwargs(self, call: ast.Call) -> None:
        for kw in call.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                self.donates = True
            elif kw.arg == "static_argnames":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(
                            c.value, str):
                        self.static_names.add(c.value)
            elif kw.arg == "static_argnums":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(
                            c.value, int):
                        self.static_nums.add(c.value)


def _jit_spec_from_decorators(fn) -> JitSpec:
    """Recognize @jax.jit / @jit / @functools.partial(jax.jit, ...)
    (any import alias of the jax module, e.g. ``_jax.jit``)."""
    spec = JitSpec()
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target)
        if name.endswith("jit") or name == "jit":
            spec.is_jit = True
            if isinstance(dec, ast.Call):
                spec.absorb_call_kwargs(dec)
            continue
        if isinstance(dec, ast.Call) and name.endswith("partial"):
            if dec.args and _dotted(dec.args[0]).endswith("jit"):
                spec.is_jit = True
                spec.absorb_call_kwargs(dec)
    return spec


def _param_names(fn) -> List[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


class _Taint:
    """Forward taint over one function body: which local names hold
    traced (device) values.  Deliberately simple — two forward sweeps
    handle the straight-line + simple-loop code kernels are written in."""

    def __init__(self, seeds: Set[str]):
        self.tainted = set(seeds)

    # -- expression query ------------------------------------------------

    def expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _UNTAINT_ATTRS:
                return False            # x.shape et al. are static under jit
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            f = _dotted(node.func)
            head = f.split(".")[0]
            if head in ("jnp", "lax"):
                return True             # device-producing call
            if f == "len":
                return False
            if isinstance(node.func, ast.Attribute) and self.expr(
                    node.func.value):
                return True             # method on a traced value
            return any(self.expr(a) for a in node.args) or any(
                self.expr(kw.value) for kw in node.keywords)
        if isinstance(node, (ast.BinOp,)):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return self.expr(node.left) or any(
                self.expr(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        return False

    # -- statement sweep -------------------------------------------------

    def _mark_target(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._mark_target(e, tainted)
        elif isinstance(target, ast.Starred):
            self._mark_target(target.value, tainted)
        # subscript/attribute stores taint the base conservatively
        elif isinstance(target, ast.Subscript) and tainted:
            self._mark_target(target.value, True)

    def sweep(self, body) -> None:
        for st in body:
            if isinstance(st, ast.Assign):
                t = self.expr(st.value)
                for tgt in st.targets:
                    self._mark_target(tgt, t)
            elif isinstance(st, ast.AugAssign):
                if self.expr(st.value):
                    self._mark_target(st.target, True)
            elif isinstance(st, ast.AnnAssign) and st.value is not None:
                self._mark_target(st.target, self.expr(st.value))
            elif isinstance(st, ast.For):
                if self.expr(st.iter):
                    self._mark_target(st.target, True)
                self.sweep(st.body)
                self.sweep(st.orelse)
            elif isinstance(st, (ast.While, ast.If)):
                self.sweep(st.body)
                self.sweep(st.orelse)
            elif isinstance(st, ast.With):
                self.sweep(st.body)
            elif isinstance(st, ast.Try):
                self.sweep(st.body)
                for h in st.handlers:
                    self.sweep(h.body)
                self.sweep(st.orelse)
                self.sweep(st.finalbody)
            elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # nested defs (scan bodies, helpers over traced values):
                # their params are traced by construction
                self.tainted.update(_param_names(st))
                self.sweep(st.body)


def _scan_jit_body(src: SourceFile, fn, scope: str, spec: JitSpec,
                   out: List[Finding]) -> None:
    """Flag host syncs + float64 inside one jitted function."""
    params = _param_names(fn)
    seeds = {p for i, p in enumerate(params)
             if p not in spec.static_names and i not in spec.static_nums
             and p != "self"}
    taint = _Taint(seeds)
    # two sweeps: the second catches names that became tainted after
    # their first textual use (simple loops)
    taint.sweep(fn.body)
    taint.sweep(fn.body)

    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = _dotted(node.func)
        # float(x) / int(x) / bool(x) on a traced value
        if f in _SYNC_BUILTINS and node.args and taint.expr(node.args[0]):
            fi = src.finding(
                "jax-host-sync", node, scope,
                f"{f}() on a traced value inside a jitted function — "
                "trace-time concretization (stale constant) or a "
                "device sync per call")
            if fi:
                out.append(fi)
        # x.item() / x.tolist()
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _SYNC_METHODS
              and taint.expr(node.func.value)):
            fi = src.finding(
                "jax-host-sync", node, scope,
                f".{node.func.attr}() on a traced value inside a "
                "jitted function — implicit device sync")
            if fi:
                out.append(fi)
        # np.asarray / np.array on a traced value
        elif (f.split(".")[0] in ("np", "numpy")
              and f.split(".")[-1] in ("asarray", "array")
              and node.args and taint.expr(node.args[0])):
            fi = src.finding(
                "jax-host-sync", node, scope,
                f"{f}() on a traced value inside a jitted function — "
                "blocking device->host pull on the hot path")
            if fi:
                out.append(fi)
        # explicit float64
        if ((isinstance(node.func, ast.Attribute)
             and node.func.attr == "astype"
             and node.args
             and _dotted(node.args[0]).endswith("float64"))
                or f.endswith(".float64")):
            fi = src.finding(
                "jax-float64", node, scope,
                "explicit float64 inside a jitted function — silently "
                "float32 under default x64=off, 2x HBM traffic when on")
            if fi:
                out.append(fi)
        for kw in node.keywords:
            if kw.arg == "dtype" and _dotted(kw.value).endswith("float64"):
                fi = src.finding(
                    "jax-float64", kw.value, scope,
                    "dtype=float64 inside a jitted function — silently "
                    "float32 under default x64=off")
                if fi:
                    out.append(fi)


def _module_mutable_globals(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for st in tree.body:
        if isinstance(st, ast.Assign) and isinstance(
                st.value, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                           ast.DictComp, ast.SetComp)):
            for tgt in st.targets:
                if isinstance(tgt, ast.Name):
                    out.add(tgt.id)
    return out


def _check_jit_signature(src: SourceFile, fn, scope: str, spec: JitSpec,
                         out: List[Finding]) -> None:
    params = _param_names(fn)
    # ring-buffer args without donation
    rings = [p for p in params
             if p in _RING_NAMES or p.startswith(_RING_PREFIXES)]
    if rings and not spec.donates:
        fi = src.finding(
            "jax-donate-missing", fn, scope,
            f"jitted function takes ring-buffer arg(s) "
            f"{', '.join(rings)} without donate_argnums/donate_argnames "
            "— every step copies the ring instead of aliasing in place")
        if fi:
            out.append(fi)
    # static_argnames whose default is unhashable
    defaults = fn.args.defaults
    pos = fn.args.posonlyargs + fn.args.args
    padded = [None] * (len(pos) - len(defaults)) + list(defaults)
    for p, d in zip(pos, padded):
        if p.arg in spec.static_names and isinstance(
                d, (ast.List, ast.Dict, ast.Set)):
            fi = src.finding(
                "jax-nonhashable-static", d, scope,
                f"static arg {p.arg!r} has an unhashable default — "
                "jit raises at the first defaulted call")
            if fi:
                out.append(fi)
    kw_defaults = dict(zip([a.arg for a in fn.args.kwonlyargs],
                           fn.args.kw_defaults))
    for name, d in kw_defaults.items():
        if name in spec.static_names and isinstance(
                d, (ast.List, ast.Dict, ast.Set)):
            fi = src.finding(
                "jax-nonhashable-static", d, scope,
                f"static arg {name!r} has an unhashable default — "
                "jit raises at the first defaulted call")
            if fi:
                out.append(fi)
    # str/bool-annotated params not marked static
    for i, p in enumerate(pos + fn.args.kwonlyargs):
        ann = getattr(p, "annotation", None)
        if ann is None:
            continue
        tname = _dotted(ann)
        if tname in ("str", "bool") and p.arg not in spec.static_names \
                and i not in spec.static_nums:
            fi = src.finding(
                "jax-unmarked-static", p, scope,
                f"param {p.arg!r} annotated {tname} on a jitted function "
                "but not in static_argnames — strings fail at trace "
                "time, traced bools break Python branching")
            if fi:
                out.append(fi)


def _check_global_capture(src: SourceFile, fn, scope: str,
                          mutable_globals: Set[str],
                          out: List[Finding]) -> None:
    local = set(_param_names(fn))
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        local.add(n.id)
    for node in ast.walk(fn):
        if (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)
                and node.id in mutable_globals and node.id not in local):
            fi = src.finding(
                "jax-mutable-global-capture", node, scope,
                f"module-level mutable {node.id!r} read inside a jitted "
                "function — the trace snapshots it; later mutations "
                "never invalidate the compiled executable")
            if fi:
                out.append(fi)


def _resolve_local_fn(name: str, module: ast.Module,
                      parent_body) -> Optional[ast.FunctionDef]:
    for body in (parent_body, module.body):
        for st in body:
            if isinstance(st, ast.FunctionDef) and st.name == name:
                return st
    return None


def _iter_jitted_functions(src: SourceFile):
    """Yield (fn, scope, spec) for decorator-style AND call-style jit
    (``step = jax.jit(fn, ...)`` / ``jax.jit(shard_map(inner, ...))``)."""
    module = src.tree
    stack = [(module, "")]
    while stack:
        node, prefix = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                stack.append((child, child.name))
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = f"{prefix}.{child.name}" if prefix else child.name
                if isinstance(child, ast.FunctionDef):
                    spec = _jit_spec_from_decorators(child)
                    if spec.is_jit:
                        yield child, scope, spec
                stack.append((child, scope))
    # call-style: jax.jit(f, ...) where f is a local def (possibly
    # wrapped in shard_map(...))
    for call in ast.walk(module):
        if not isinstance(call, ast.Call):
            continue
        if not _dotted(call.func).endswith("jit"):
            continue
        if not call.args:
            continue
        spec = JitSpec()
        spec.is_jit = True
        spec.absorb_call_kwargs(call)
        inner = call.args[0]
        if isinstance(inner, ast.Call):        # jit(shard_map(f, ...))
            spec.absorb_call_kwargs(inner)
            inner = inner.args[0] if inner.args else None
        if isinstance(inner, ast.Name):
            fn = _resolve_local_fn(inner.id, module, module.body)
            if fn is not None:
                yield fn, fn.name, spec


def _check_hot_roundtrip(src: SourceFile, fn, scope: str,
                         out: List[Finding]) -> None:
    """Eager hot-path rule: np.asarray pull whose result feeds a
    jnp.asarray/device_put re-upload in the same function."""
    pulled: Set[str] = set()

    def value_is_pull(node: ast.AST) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                f = _dotted(n.func)
                if (f.split(".")[0] in ("np", "numpy")
                        and f.split(".")[-1] in ("asarray", "array")):
                    return True
            if isinstance(n, ast.Name) and n.id in pulled:
                return True
        return False

    class V(ast.NodeVisitor):
        def visit_Assign(self, node):
            if value_is_pull(node.value):
                for tgt in node.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            pulled.add(n.id)
                        elif isinstance(n, ast.Subscript) and isinstance(
                                n.value, ast.Name):
                            pulled.add(n.value.id)
            self.generic_visit(node)

        def visit_Call(self, node):
            f = _dotted(node.func)
            if (f in ("jnp.asarray", "jnp.array")
                    or f.endswith("device_put")) and node.args:
                arg = node.args[0]
                if any(isinstance(n, ast.Name) and n.id in pulled
                       for n in ast.walk(arg)):
                    fi = src.finding(
                        "jax-host-roundtrip", node, scope,
                        "host value pulled with np.asarray is re-uploaded "
                        "here — a device->host->device round-trip (2 wire "
                        "crossings) for work the device can do in place")
                    if fi:
                        out.append(fi)
            self.generic_visit(node)

    V().visit(fn)


def run(src: SourceFile) -> Iterable[Finding]:
    out: List[Finding] = []
    mutable_globals = _module_mutable_globals(src.tree)
    seen = set()
    for fn, scope, spec in _iter_jitted_functions(src):
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        _scan_jit_body(src, fn, scope, spec, out)
        _check_jit_signature(src, fn, scope, spec, out)
        if mutable_globals:
            _check_global_capture(src, fn, scope, mutable_globals, out)
    # eager-context hot-path round-trips (models orchestration methods)
    stack = [(src.tree, "")]
    while stack:
        node, prefix = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                stack.append((child, child.name))
            elif isinstance(child, ast.FunctionDef):
                scope = f"{prefix}.{child.name}" if prefix else child.name
                if child.name.startswith(HOT_PATH_PREFIXES):
                    _check_hot_roundtrip(src, child, scope, out)
    return out


register_pass("jax-pass", JAX_SCOPE, run)
