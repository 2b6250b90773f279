"""AST lint pass: recompile hazards and Pallas legality over ``src/``.

The analyzer is purely static — it never imports the code under analysis.
Per module it runs three passes:

1. **Traced-context discovery** — find every function that JAX will trace:
   ``@jax.jit`` / ``@functools.partial(jax.jit, ...)`` decorated defs,
   ``jax.jit(fn)`` / ``jax.jit(lambda ...)`` / ``jax.jit(self._method)``
   call sites, ``jax.jit(self._make_x(...))`` *factory* calls (every def
   nested inside ``_make_x`` is traced), ``jax.lax.scan`` bodies, and
   ``pl.pallas_call`` kernels (including the ``functools.partial(kern,
   ...)`` indirection).  ``static_argnames``/``static_argnums`` are
   honoured; Pallas kernels treat keyword-only params as static config
   (the repo-wide convention — positional params are refs).

2. **Taint walk** per traced context — params are traced values; taint
   propagates through assignments/unpacking; ``.shape``/``.dtype``/
   ``.ndim``/``.size`` access and static params launder it.  The TRC rules
   fire on hazardous uses of tainted values.

3. **Pallas legality** — BlockSpec/VMEM tile shapes (lane %128, sublane
   %8), grid/index_map arity, ``interpret=`` plumbing, and the
   module-level ban on ``jax.default_backend()`` probes outside
   ``kernels/backend.py``.

4. **Poll hot-loop sync hygiene** (SYN rules) — in classes that define
   ``poll()`` and register jitted stages on ``self``, the hot methods
   must not concretize stage outputs implicitly (``.item()``, ``int()``,
   ``np.asarray`` on a device value) or stall the dispatch queue
   (``block_until_ready``); the only legal readback is an explicit
   ``jax.device_get``, batched per readback window.

Dims are resolved through literal assignments, parameter defaults and
simple arithmetic; anything unresolvable is skipped, never guessed.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple, Union

from repro.analysis.callgraph import (CallGraph, INTERPROC_RULE,
                                      MAX_CHAIN_DEPTH, format_chain,
                                      func_display_name, map_tainted_params)
from repro.analysis.report import Finding, sort_findings
from repro.analysis.rules import RULES

FuncNode = Union[ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda]
_FUNC_TYPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

# attribute access that yields static (python) metadata, not a traced value
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "weak_type"}
# host-side numpy module aliases
_NUMPY_ALIASES = {"np", "numpy", "onp"}
# jnp constructors whose result is a device array (closure-capture hazard)
_DEVICE_CONSTRUCTORS = {"array", "asarray", "zeros", "ones", "full", "arange",
                        "linspace", "eye", "zeros_like", "ones_like",
                        "full_like"}
# poll-hot-loop method names (SYN rules): the scheduler/cluster round
# entry points plus their dispatch/commit helpers
_HOT_METHOD_NAMES = {"poll", "step", "tick", "prefill_poll"}
_HOT_METHOD_PREFIXES = ("_step", "_poll", "_dispatch", "_commit")


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name for a call target: ``jax.lax.scan`` etc."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit(name: str) -> bool:
    return name in ("jit", "jax.jit", "pjit", "jax.pjit")


def _is_scan(name: str) -> bool:
    return name.endswith("lax.scan")


def _is_pallas_call(name: str) -> bool:
    return name == "pallas_call" or name.endswith(".pallas_call")


def _is_partial(name: str) -> bool:
    return name in ("partial", "functools.partial")


class _TracedMark:
    """Why a function is traced and which params are static."""

    def __init__(self, kind: str, statics: Set[str], origin: ast.AST):
        self.kind = kind                    # "jit" | "scan" | "pallas"
        self.statics = statics
        self.origin = origin


def _static_names_from_call(call: ast.Call, fn: Optional[FuncNode]
                            ) -> Set[str]:
    """Extract static_argnames / static_argnums from a jit(...) call."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    if isinstance(el, ast.Constant) and isinstance(el.value, str):
                        out.add(el.value)
        elif kw.arg == "static_argnums" and fn is not None \
                and not isinstance(fn, ast.Lambda):
            nums: List[int] = []
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = [el.value for el in v.elts
                        if isinstance(el, ast.Constant)
                        and isinstance(el.value, int)]
            pos = [a.arg for a in fn.args.posonlyargs + fn.args.args]
            for n in nums:
                if 0 <= n < len(pos):
                    out.add(pos[n])
    return out


def _param_names(fn: FuncNode) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


class ModuleLinter:
    """Lints one parsed module."""

    def __init__(self, tree: ast.Module, source: str, path: str):
        self.tree = tree
        self.lines = source.splitlines()
        self.path = path
        self.findings: List[Finding] = []
        self._annotate_parents()
        self.defs_by_name: Dict[str, List[FuncNode]] = {}
        self.all_calls: List[ast.Call] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(node.name, []).append(node)
            elif isinstance(node, ast.Call):
                self.all_calls.append(node)
        self.traced: Dict[int, _TracedMark] = {}    # id(node) -> mark
        self._node_by_id: Dict[int, FuncNode] = {}
        self.callgraph = CallGraph(self.defs_by_name)

    # -- plumbing -----------------------------------------------------------
    def _annotate_parents(self) -> None:
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._parent = node              # type: ignore[attr-defined]

    def _snippet(self, node: ast.AST) -> str:
        ln = getattr(node, "lineno", 0)
        if 1 <= ln <= len(self.lines):
            return self.lines[ln - 1].strip()
        return ""

    def emit(self, rule: str, node: ast.AST, message: str) -> None:
        meta = RULES[rule]
        self.findings.append(Finding(
            rule=rule, path=self.path,
            line=getattr(node, "lineno", 0), col=getattr(node, "col_offset", 0),
            severity=meta.severity,
            message=f"[{meta.name}] {message}",
            snippet=self._snippet(node)))

    def _enclosing_funcs(self, node: ast.AST) -> List[FuncNode]:
        out: List[FuncNode] = []
        cur = getattr(node, "_parent", None)
        while cur is not None:
            if isinstance(cur, _FUNC_TYPES):
                out.append(cur)
            cur = getattr(cur, "_parent", None)
        return out

    # -- pass 1: traced-context discovery -----------------------------------
    def _mark(self, fn: FuncNode, kind: str, statics: Set[str],
              origin: ast.AST) -> None:
        if id(fn) not in self.traced:
            self.traced[id(fn)] = _TracedMark(kind, statics, origin)
            self._node_by_id[id(fn)] = fn

    def _resolve_callable(self, expr: ast.AST) -> List[FuncNode]:
        """Resolve an expression passed as a traceable callable to defs."""
        if isinstance(expr, ast.Lambda):
            return [expr]
        if isinstance(expr, ast.Name):
            if expr.id in self.defs_by_name:
                return list(self.defs_by_name[expr.id])
            # name assigned from functools.partial(kern, ...)
            for node in ast.walk(self.tree):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and node.targets[0].id == expr.id \
                        and isinstance(node.value, ast.Call) \
                        and _is_partial(_dotted(node.value.func)) \
                        and node.value.args:
                    return self._resolve_callable(node.value.args[0])
            return []
        if isinstance(expr, ast.Attribute):
            # self._method / module.fn — best effort within this module
            return list(self.defs_by_name.get(expr.attr, []))
        if isinstance(expr, ast.Call):
            name = _dotted(expr.func)
            if _is_partial(name) and expr.args:
                return self._resolve_callable(expr.args[0])
        return []

    def discover_traced(self) -> None:
        # decorators
        for defs in self.defs_by_name.values():
            for fn in defs:
                for dec in fn.decorator_list:
                    if _is_jit(_dotted(dec)):
                        self._mark(fn, "jit", set(), dec)
                    elif isinstance(dec, ast.Call):
                        dname = _dotted(dec.func)
                        if _is_jit(dname):
                            self._mark(fn, "jit",
                                       _static_names_from_call(dec, fn), dec)
                        elif _is_partial(dname) and dec.args \
                                and _is_jit(_dotted(dec.args[0])):
                            self._mark(fn, "jit",
                                       _static_names_from_call(dec, fn), dec)
        # call sites
        for call in self.all_calls:
            name = _dotted(call.func)
            if _is_jit(name) and call.args:
                target = call.args[0]
                resolved = self._resolve_callable(target)
                if resolved:
                    for fn in resolved:
                        self._mark(fn, "jit",
                                   _static_names_from_call(call, fn), call)
                elif isinstance(target, ast.Call):
                    # factory pattern: jax.jit(self._make_x(...)) — the defs
                    # nested inside the factory are what gets traced.
                    for factory in self._resolve_callable(target.func):
                        if isinstance(factory, ast.Lambda):
                            continue
                        for sub in ast.walk(factory):
                            if sub is not factory \
                                    and isinstance(sub, _FUNC_TYPES):
                                self._mark(sub, "jit", set(), call)
            elif _is_scan(name) and call.args:
                for fn in self._resolve_callable(call.args[0]):
                    self._mark(fn, "scan", set(), call)
            elif _is_pallas_call(name) and call.args:
                for fn in self._resolve_callable(call.args[0]):
                    statics: Set[str] = set()
                    if not isinstance(fn, ast.Lambda):
                        # repo convention: kernel keyword-only params are
                        # static config bound via functools.partial
                        statics = {a.arg for a in fn.args.kwonlyargs}
                    self._mark(fn, "pallas", statics, call)

    # -- pass 2: taint walk over each traced context ------------------------
    def check_traced(self) -> None:
        # nested traced fns are walked as part of their traced parent
        roots = []
        for fid, mark in self.traced.items():
            fn = self._node_by_id[fid]
            if not any(id(enc) in self.traced
                       for enc in self._enclosing_funcs(fn)):
                roots.append((fn, mark))
        for fn, mark in roots:
            _TaintWalker(self, fn, mark).run()
            if mark.kind == "jit":
                self._check_closure_capture(fn)

    def _check_closure_capture(self, fn: FuncNode) -> None:
        """TRC006: device arrays captured by a jitted closure."""
        bound: Set[str] = set(_param_names(fn))
        free: List[ast.Name] = []
        for node in ast.walk(fn):
            if isinstance(node, _FUNC_TYPES) and node is not fn:
                bound.update(_param_names(node))
            elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            bound.add(n.id)
            elif isinstance(node, ast.comprehension):
                for n in ast.walk(node.target):
                    if isinstance(n, ast.Name):
                        bound.add(n.id)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                    and node.id not in bound:
                free.append(node)
        seen: Set[str] = set()
        for name_node in free:
            nm = name_node.id
            if nm in seen:
                continue
            seen.add(nm)
            for enc in self._enclosing_funcs(fn):
                for node in ast.walk(enc):
                    if isinstance(node, _FUNC_TYPES) and node is not enc:
                        continue
                    if isinstance(node, ast.Assign) \
                            and any(isinstance(t, ast.Name) and t.id == nm
                                    for t in node.targets) \
                            and self._is_device_constructor(node.value):
                        self.emit(
                            "TRC006", name_node,
                            f"'{nm}' is a device array captured by this "
                            f"jitted closure; pass it as an argument")

    def _is_device_constructor(self, expr: ast.AST) -> bool:
        if not isinstance(expr, ast.Call):
            return False
        name = _dotted(expr.func)
        parts = name.split(".")
        if len(parts) >= 2 and parts[0] in ("jnp", "jax") \
                and parts[-1] in _DEVICE_CONSTRUCTORS:
            return True
        return name in ("jax.device_put",)

    # -- pass 3: pallas legality --------------------------------------------
    def check_pallas(self) -> None:
        for call in self.all_calls:
            name = _dotted(call.func)
            if name.endswith("BlockSpec") or name.endswith("pltpu.VMEM") \
                    or name == "VMEM":
                self._check_tile_shape(call)
            if _is_pallas_call(name):
                self._check_pallas_call(call)
            if name == "jax.default_backend" \
                    and not self.path.replace(os.sep, "/").endswith(
                        "kernels/backend.py"):
                self.emit("PLT005", call,
                          "backend probe outside kernels/backend.py")
            self._check_page_size(call)

    def _check_page_size(self, call: ast.Call) -> None:
        """PLT006: any resolvable ``page_size=`` keyword must be a positive
        multiple of 8 — KV pages occupy the kernel sublane dimension."""
        for kw in call.keywords:
            if kw.arg != "page_size":
                continue
            scope_list = self._enclosing_funcs(call)
            scope = scope_list[0] if scope_list else self.tree
            v = self._resolve_int(kw.value, scope)
            if v is None:
                continue
            if v <= 0 or v % 8 != 0:
                self.emit("PLT006", kw.value,
                          f"page_size={v} is not a positive multiple of 8 "
                          f"(sublane-illegal KV pages)")

    def _resolve_int(self, expr: ast.AST, scope: Optional[ast.AST]
                     ) -> Optional[int]:
        """Resolve an int through literals, assignments, param defaults and
        simple arithmetic.  Returns None when ambiguous."""
        if isinstance(expr, ast.Constant):
            return expr.value if isinstance(expr.value, int) else None
        if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
            v = self._resolve_int(expr.operand, scope)
            return -v if v is not None else None
        if isinstance(expr, ast.BinOp):
            l = self._resolve_int(expr.left, scope)
            r = self._resolve_int(expr.right, scope)
            if l is None or r is None:
                return None
            try:
                if isinstance(expr.op, ast.Add):
                    return l + r
                if isinstance(expr.op, ast.Sub):
                    return l - r
                if isinstance(expr.op, ast.Mult):
                    return l * r
                if isinstance(expr.op, ast.FloorDiv):
                    return l // r
                if isinstance(expr.op, ast.Mod):
                    return l % r
            except (ZeroDivisionError, ValueError):
                return None
            return None
        if isinstance(expr, ast.Name):
            vals: Set[int] = set()
            for enc in ([scope] if scope is not None else []):
                for node in ast.walk(enc):
                    if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                            and isinstance(node.targets[0], ast.Name) \
                            and node.targets[0].id == expr.id:
                        v = self._resolve_int(node.value, scope)
                        if v is None:
                            return None
                        vals.add(v)
                if isinstance(enc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    a = enc.args
                    pos = a.posonlyargs + a.args
                    for p, d in zip(pos[len(pos) - len(a.defaults):],
                                    a.defaults):
                        if p.arg == expr.id:
                            v = self._resolve_int(d, scope)
                            if v is not None:
                                vals.add(v)
                    for p, d in zip(a.kwonlyargs, a.kw_defaults):
                        if p.arg == expr.id and d is not None:
                            v = self._resolve_int(d, scope)
                            if v is not None:
                                vals.add(v)
            if len(vals) == 1:
                return vals.pop()
            return None
        return None

    def _check_tile_shape(self, call: ast.Call) -> None:
        shape = None
        if call.args and isinstance(call.args[0], ast.Tuple):
            shape = call.args[0]
        for kw in call.keywords:
            if kw.arg in ("block_shape", "shape") \
                    and isinstance(kw.value, ast.Tuple):
                shape = kw.value
        if shape is None or len(shape.elts) < 1:
            return
        scope_list = self._enclosing_funcs(call)
        scope = scope_list[0] if scope_list else self.tree
        dims = [self._resolve_int(e, scope) for e in shape.elts]
        last = dims[-1]
        if last is not None and last != 1 and last % 128 != 0:
            self.emit("PLT001", shape.elts[-1],
                      f"block last dim {last} is not a multiple of 128 "
                      f"(lane width)")
        if len(dims) >= 2:
            sub = dims[-2]
            if sub is not None and sub != 1 and sub % 8 != 0:
                self.emit("PLT002", shape.elts[-2],
                          f"block sublane dim {sub} is not a multiple of 8 "
                          f"(f32 sublane)")

    def _check_pallas_call(self, call: ast.Call) -> None:
        kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        if "interpret" not in kwargs:
            self.emit("PLT003", call,
                      "pallas_call without interpret= plumbing (no CPU "
                      "fallback path)")
        grid_rank = self._grid_rank(kwargs.get("grid"), call)
        if grid_rank is None:
            return
        for key in ("in_specs", "out_specs"):
            specs = kwargs.get(key)
            if specs is None:
                continue
            spec_calls: List[ast.Call] = []
            if isinstance(specs, (ast.List, ast.Tuple)):
                spec_calls = [e for e in specs.elts if isinstance(e, ast.Call)]
            elif isinstance(specs, ast.Call):
                spec_calls = [specs]
            for sc in spec_calls:
                if not _dotted(sc.func).endswith("BlockSpec"):
                    continue
                self._check_index_map(sc, grid_rank)

    def _grid_rank(self, grid: Optional[ast.AST], call: ast.Call
                   ) -> Optional[int]:
        if grid is None:
            return None
        if isinstance(grid, ast.Tuple):
            return len(grid.elts)
        if isinstance(grid, ast.Constant) and isinstance(grid.value, int):
            return 1
        if isinstance(grid, ast.Name):
            for enc in self._enclosing_funcs(call) + [self.tree]:
                for node in ast.walk(enc):
                    if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                            and isinstance(node.targets[0], ast.Name) \
                            and node.targets[0].id == grid.id \
                            and isinstance(node.value, ast.Tuple):
                        return len(node.value.elts)
        return None

    def _check_index_map(self, spec: ast.Call, grid_rank: int) -> None:
        shape = spec.args[0] if spec.args \
            and isinstance(spec.args[0], ast.Tuple) else None
        index_map = None
        if len(spec.args) >= 2:
            index_map = spec.args[1]
        for kw in spec.keywords:
            if kw.arg == "index_map":
                index_map = kw.value
        if not isinstance(index_map, ast.Lambda):
            return
        arity = len(index_map.args.args) + len(index_map.args.posonlyargs)
        if not index_map.args.vararg and arity != grid_rank:
            self.emit("PLT004", index_map,
                      f"index_map takes {arity} args but grid rank is "
                      f"{grid_rank}")
        if shape is not None and isinstance(index_map.body, ast.Tuple) \
                and len(index_map.body.elts) != len(shape.elts):
            self.emit("PLT004", index_map,
                      f"index_map returns {len(index_map.body.elts)} coords "
                      f"for a rank-{len(shape.elts)} block")

    # -- pass 4: host-sync hazards in poll hot loops (SYN rules) -------------
    def check_poll_sync(self) -> None:
        """Flag implicit device syncs inside the serving poll hot loop.

        Scope: classes that define ``poll`` AND assign jitted stages to
        ``self`` attributes (``self._step = jax.jit(...)``).  Inside that
        class's hot methods (``poll``/``step``/``tick``/``prefill_poll``
        and ``_step*``/``_poll*``/``_dispatch*``/``_commit*`` helpers),
        values produced by calling those stages are *device* values:
        concretizing one without an explicit ``jax.device_get`` is a
        hidden host sync (SYN001/SYN002), and ``block_until_ready`` is a
        pipeline stall (SYN003).  ``jax.device_get(...)`` launders the
        taint — the legal batched-readback idiom
        ``np.asarray(jax.device_get(ring))`` never fires."""
        for cls in ast.walk(self.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = [n for n in cls.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            if not any(m.name == "poll" for m in methods):
                continue
            jit_attrs = self._jit_stage_attrs(cls)
            if not jit_attrs:
                continue
            dev_attrs = self._device_state_attrs(cls, jit_attrs)
            for m in methods:
                if m.name in _HOT_METHOD_NAMES \
                        or m.name.startswith(_HOT_METHOD_PREFIXES):
                    _PollSyncWalker(self, m, jit_attrs, dev_attrs).run()

    @staticmethod
    def _jit_stage_attrs(cls: ast.ClassDef) -> Set[str]:
        """``self.x`` attributes assigned from ``jax.jit(...)`` anywhere
        in the class — the pool's registered jitted stages."""
        out: Set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _is_jit(_dotted(node.value.func)):
                for t in node.targets:
                    d = _dotted(t)
                    if d.startswith("self."):
                        out.add(d)
        return out

    @staticmethod
    def _device_state_attrs(cls: ast.ClassDef, jit_attrs: Set[str]
                            ) -> Set[str]:
        """``self.x`` attributes assigned (anywhere in the class) directly
        from a jitted-stage call — cross-method device state like a cache
        handle or token ring."""
        out: Set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _dotted(node.value.func) in jit_attrs:
                for t in node.targets:
                    d = _dotted(t)
                    if d.startswith("self."):
                        out.add(d)
        return out

    # -- driver -------------------------------------------------------------
    def run(self) -> List[Finding]:
        self.discover_traced()
        self.check_traced()
        self.check_pallas()
        self.check_poll_sync()
        return self.findings


class _TaintWalker:
    """Walks one traced function, propagating taint and firing TRC rules.

    With a non-empty ``chain`` the walker is re-entered *interprocedurally*
    — inside a same-module helper reached from a traced root — and fires
    the IPC translation of each TRC rule instead, carrying the chain in
    the message (see :mod:`repro.analysis.callgraph`)."""

    def __init__(self, linter: ModuleLinter, fn: FuncNode, mark: _TracedMark,
                 chain: Tuple[str, ...] = (),
                 tainted_params: Optional[Set[str]] = None,
                 visited: Optional[Set[Tuple[int, frozenset]]] = None):
        self.linter = linter
        self.fn = fn
        self.mark = mark
        self.chain = chain or (func_display_name(fn),)
        self.visited = visited if visited is not None else set()
        self.tainted: Set[str] = set()
        if tainted_params is not None:     # helper mode: caller decides
            self.tainted = set(tainted_params)
        else:
            for name in _param_names(fn):
                if name in ("self", "cls") or name in mark.statics:
                    continue
                self.tainted.add(name)

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        """Emit a finding; inside a followed helper the rule becomes its
        IPC counterpart and the message names the full call chain."""
        if len(self.chain) > 1:
            mapped = INTERPROC_RULE.get(rule)
            if mapped is None:
                return
            self.linter.emit(
                mapped, node,
                f"{message} [call chain: {format_chain(self.chain)}]")
        else:
            self.linter.emit(rule, node, message)

    # taintedness of an expression -----------------------------------------
    def _is_tainted(self, expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Attribute) \
                    and node.attr in _STATIC_ATTRS:
                # .shape/.dtype/... launder taint: prune by checking the
                # name is only reached through the static attribute.
                continue
            if isinstance(node, ast.Name) and node.id in self.tainted:
                if self._reached_via_static_attr(expr, node):
                    continue
                return True
        return False

    def _reached_via_static_attr(self, root: ast.AST, target: ast.Name
                                 ) -> bool:
        """True if every path from root to target goes through a static
        attribute access (x.shape and friends)."""
        cur: Optional[ast.AST] = getattr(target, "_parent", None)
        while cur is not None and cur is not getattr(root, "_parent", None):
            if isinstance(cur, ast.Attribute) and cur.attr in _STATIC_ATTRS:
                return True
            cur = getattr(cur, "_parent", None)
        return False

    def _taint_target(self, target: ast.AST) -> None:
        for node in ast.walk(target):
            if isinstance(node, ast.Name):
                self.tainted.add(node.id)

    # statement / expression walk ------------------------------------------
    def run(self) -> None:
        body = self.fn.body if not isinstance(self.fn, ast.Lambda) \
            else [ast.Expr(value=self.fn.body)]
        for stmt in body:
            self._walk(stmt)

    def _walk(self, node: ast.AST) -> None:
        if isinstance(node, _FUNC_TYPES):
            # nested def inside a traced context: its params receive traced
            # values (scan bodies, tree.map lambdas); analyze inline.
            for name in _param_names(node):
                self.tainted.add(name)
            inner = node.body if not isinstance(node, ast.Lambda) \
                else [ast.Expr(value=node.body)]
            for stmt in inner:
                self._walk(stmt)
            return
        if isinstance(node, ast.Assign):
            self._walk(node.value)
            if self._is_tainted(node.value):
                for t in node.targets:
                    self._taint_target(t)
            return
        if isinstance(node, ast.AugAssign):
            self._walk(node.value)
            if self._is_tainted(node.value):
                self._taint_target(node.target)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._walk(node.value)
                if self._is_tainted(node.value):
                    self._taint_target(node.target)
            return
        if isinstance(node, (ast.If, ast.While)):
            self._walk(node.test)
            if self._is_tainted(node.test) \
                    and not self._exempt_test(node.test):
                self._emit(
                    "TRC004", node,
                    "branch condition depends on a traced value")
            for stmt in node.body + node.orelse:
                self._walk(stmt)
            return
        if isinstance(node, ast.For):
            self._walk(node.iter)
            if self._is_tainted(node.iter):
                self._emit(
                    "TRC004", node,
                    "loop iterates over a traced value (unrolls / "
                    "concretizes at trace time)")
                self._taint_target(node.target)
            for stmt in node.body + node.orelse:
                self._walk(stmt)
            return
        if isinstance(node, ast.Assert):
            self._walk(node.test)
            if self._is_tainted(node.test) \
                    and not self._exempt_test(node.test):
                self._emit(
                    "TRC004", node,
                    "assert on a traced value concretizes it at trace time")
            return
        if isinstance(node, ast.Call):
            self._check_call(node)
            for child in ast.iter_child_nodes(node):
                self._walk(child)
            return
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue) \
                        and self._is_tainted(v.value):
                    self._emit(
                        "TRC005", node,
                        "f-string formats a traced value")
                    break
            return
        if isinstance(node, ast.comprehension):
            self._walk(node.iter)
            if self._is_tainted(node.iter):
                self._taint_target(node.target)
            for cond in node.ifs:
                self._walk(cond)
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child)

    def _exempt_test(self, test: ast.AST) -> bool:
        """Patterns that look tainted but are static: identity checks
        against None and constant-membership probes on containers."""
        if isinstance(test, ast.Compare) and len(test.ops) == 1:
            op = test.ops[0]
            if isinstance(op, (ast.Is, ast.IsNot)):
                operands = [test.left] + test.comparators
                if any(isinstance(o, ast.Constant) and o.value is None
                       for o in operands):
                    return True
            if isinstance(op, (ast.In, ast.NotIn)) \
                    and isinstance(test.left, ast.Constant):
                return True
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._exempt_test(test.operand)
        return False

    def _check_call(self, call: ast.Call) -> None:
        func = call.func
        if isinstance(func, ast.Name):
            if func.id in ("int", "float", "bool", "complex") \
                    and any(self._is_tainted(a) for a in call.args):
                self._emit(
                    "TRC001", call,
                    f"{func.id}() on a traced value (host sync + "
                    f"recompile per distinct value)")
            elif func.id == "len" \
                    and any(self._is_tainted(a) for a in call.args):
                self._emit(
                    "TRC003", call, "len() on a traced value")
        elif isinstance(func, ast.Attribute):
            if func.attr in ("item", "tolist") \
                    and self._is_tainted(func.value):
                self._emit(
                    "TRC002", call,
                    f".{func.attr}() forces a device->host sync in "
                    f"traced code")
            else:
                root = func.value
                while isinstance(root, ast.Attribute):
                    root = root.value
                if isinstance(root, ast.Name) \
                        and root.id in _NUMPY_ALIASES \
                        and any(self._is_tainted(a) for a in call.args):
                    self._emit(
                        "TRC007", call,
                        f"host numpy call {_dotted(func)}() on a traced "
                        f"value")
        self._follow_helper_call(call)

    def _follow_helper_call(self, call: ast.Call) -> None:
        """Interprocedural step: re-enter a same-module helper that
        receives tainted arguments, with the call chain recorded (IPC
        rules fire inside it).  Helpers that are traced contexts — or
        nested inside one — are covered by their own walk and skipped."""
        if len(self.chain) >= MAX_CHAIN_DEPTH:
            return
        for helper in self.linter.callgraph.resolve_call(call):
            if id(helper) in self.linter.traced:
                continue
            if any(id(enc) in self.linter.traced
                   for enc in self.linter._enclosing_funcs(helper)):
                continue
            tainted = map_tainted_params(call, helper, self._is_tainted)
            if not tainted:
                continue
            key = (id(helper), frozenset(tainted))
            if key in self.visited:
                continue
            self.visited.add(key)
            _TaintWalker(
                self.linter, helper, self.mark,
                chain=self.chain + (func_display_name(helper),),
                tainted_params=tainted, visited=self.visited).run()


class _PollSyncWalker:
    """Walks one poll-hot method, tracking which local values are outputs
    of the class's jitted stages (device values) and firing the SYN rules
    on implicit host syncs.  ``jax.device_get(...)`` launders the taint:
    the batched-readback idiom ``np.asarray(jax.device_get(x))`` and the
    explicit ``int(jax.device_get(x))`` commit read are both legal.  So
    does ``self._sync(what, x)``, the scheduler's one helper around
    ``jax.device_get``."""

    _DEVICE_GET = {"jax.device_get", "device_get", "self._sync"}

    def __init__(self, linter: ModuleLinter, fn: FuncNode,
                 jit_attrs: Set[str], dev_attrs: Set[str]):
        self.linter = linter
        self.fn = fn
        self.jit_attrs = jit_attrs
        self.dev = set(dev_attrs)          # dotted self.x device state
        self.tainted: Set[str] = set()     # local names holding device vals

    # taintedness of an expression ------------------------------------------
    def _tainted(self, expr: ast.AST) -> bool:
        if isinstance(expr, ast.Call):
            d = _dotted(expr.func)
            if d in self._DEVICE_GET:
                return False               # explicit sync launders
            if d in self.jit_attrs:
                return True                # jitted-stage output
            return any(self._tainted(c)
                       for c in ast.iter_child_nodes(expr))
        if isinstance(expr, ast.Attribute):
            if expr.attr in _STATIC_ATTRS:
                return False
            if _dotted(expr) in self.dev:
                return True
            return self._tainted(expr.value)
        if isinstance(expr, ast.Name):
            return expr.id in self.tainted
        return any(self._tainted(c) for c in ast.iter_child_nodes(expr))

    def _taint_target(self, target: ast.AST) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._taint_target(el)
            return
        if isinstance(target, ast.Starred):
            self._taint_target(target.value)
            return
        d = _dotted(target)
        if d.startswith("self."):
            self.dev.add(d)
        elif isinstance(target, ast.Name):
            self.tainted.add(target.id)
        elif isinstance(target, ast.Subscript):
            self._taint_target(target.value)

    # walk -------------------------------------------------------------------
    def run(self) -> None:
        for stmt in self.fn.body:
            self._walk(stmt)

    def _walk(self, node: ast.AST) -> None:
        if isinstance(node, _FUNC_TYPES):
            return                         # nested defs: out of scope
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            value = node.value
            if value is not None:
                self._walk(value)
                if self._tainted(value):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        self._taint_target(t)
            return
        if isinstance(node, ast.Call):
            self._check_call(node)
            for child in ast.iter_child_nodes(node):
                self._walk(child)
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child)

    def _check_call(self, call: ast.Call) -> None:
        func = call.func
        where = f"in poll hot method '{self.fn.name}'"
        if isinstance(func, ast.Name):
            if func.id in ("int", "float", "bool") \
                    and any(self._tainted(a) for a in call.args):
                self.linter.emit(
                    "SYN001", call,
                    f"{func.id}() on a jitted-stage output {where}: hidden "
                    f"per-call device sync (wrap in jax.device_get at the "
                    f"batched readback point)")
            return
        if not isinstance(func, ast.Attribute):
            return
        d = _dotted(func)
        if func.attr in ("item", "tolist") and self._tainted(func.value):
            self.linter.emit(
                "SYN001", call,
                f".{func.attr}() on a jitted-stage output {where}: hidden "
                f"per-call device sync (defer to the batched "
                f"jax.device_get readback)")
            return
        if func.attr == "block_until_ready" \
                or d == "jax.block_until_ready":
            self.linter.emit(
                "SYN003", call,
                f"block_until_ready {where} stalls the host per dispatch "
                f"— the batched readback already synchronizes")
            return
        root = func.value
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name) and root.id in _NUMPY_ALIASES \
                and any(self._tainted(a) for a in call.args):
            self.linter.emit(
                "SYN002", call,
                f"{d}() on a jitted-stage output {where} without an "
                f"explicit jax.device_get: hidden blocking transfer "
                f"(use np.asarray(jax.device_get(x)) at the readback "
                f"boundary)")


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def lint_source(source: str, path: str = "<string>") -> List[Finding]:
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        meta = RULES["PARSE"]
        return [Finding(rule="PARSE", path=path, line=e.lineno or 0,
                        col=e.offset or 0, severity=meta.severity,
                        message=f"[{meta.name}] {e.msg}")]
    return ModuleLinter(tree, source, path).run()


def lint_file(path: str, repo_root: Optional[str] = None) -> List[Finding]:
    rel = os.path.relpath(path, repo_root) if repo_root else path
    rel = rel.replace(os.sep, "/")
    with open(path, encoding="utf-8") as f:
        source = f.read()
    return lint_source(source, rel)


def iter_python_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(root, fn)


def lint_paths(paths: Sequence[str], repo_root: Optional[str] = None
               ) -> List[Finding]:
    findings: List[Finding] = []
    for fp in iter_python_files(paths):
        findings.extend(lint_file(fp, repo_root))
    return sort_findings(findings)
