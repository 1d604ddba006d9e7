"""Layer 1: AST lint — JAX footguns ruff has no rules for.

Rules (stable ids, suppress per line with ``# graphlint: disable=EG00x`` or
``# graphlint: disable`` for all):

EG001  Python ``if``/``while``/``assert`` on a likely-traced value inside a
       jit-reachable function (``jnp``/``lax`` call or ``.any()``/``.all()``
       in the test) — raises ``TracerBoolConversionError`` at trace time or,
       worse, silently bakes one branch into the compiled graph.
EG002  Host I/O reachable from a jitted function (``print``, ``open``,
       ``time.time``/``perf_counter``/``sleep``, ``subprocess``, ...) —
       runs at *trace* time, not run time, and is a classic "why does my
       timer report 0ms" / "why did it print once" footgun.
EG003  ``numpy`` math applied to a likely-traced array inside a
       jit-reachable function — forces a host transfer + constant-folds the
       tracer, or crashes; ``jnp`` is the traced-world spelling.
EG004  ``jax.jit`` wrapping a function with config-like parameters
       (``cfg``, ``mesh``, ``capacity``, ...) that are not listed in
       ``static_argnames``/``static_argnums`` — every distinct config then
       either fails to hash or retraces silently.
EG005  Host coercion (``.item()``, ``float(...)``/``int(...)`` of computed
       values, ``jax.device_get``) inside a decode/generate hot loop — a
       device sync per token.
EG006  Mutation of a captured container (``append``/``update``/subscript
       assignment) inside a function nested under a jit-reachable one —
       the mutation happens once at trace time, not per call.
EG007  A literal metric name (``registry.counter/gauge/histogram("...")``,
       direct ``Counter``/``Gauge``/``Histogram`` construction) or span name
       (``span("...")``/``obs_span("...")``/``phase("...")``/
       ``obs_phase("...")``) or ``named_scope("...")`` name that is not in the
       registered vocabulary (``obs/names.py``) — a typo'd name silently creates a
       series no dashboard ever scrapes. f-string names lint as wildcard
       patterns against the registered templates; fully dynamic names (a
       variable) are out of scope.

Reachability: a function is *jit-reachable* when it is (a) decorated with
``jax.jit`` (directly or via ``partial``), (b) wrapped by a module-level
``NAME = jax.jit(fn, ...)``, (c) passed to a tracing wrapper
(``shard_map``, ``lax.scan``, ``vmap``, ``checkpoint``, ``cond``, ...), or
(d) called by name from a jit-reachable function (intra-module closure,
nested defs included). Host-side orchestration code is deliberately out of
scope — these rules only fire where tracing semantics apply.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .report import Finding

# -- rule vocabulary --------------------------------------------------------

#: parameters that are config-like (hashable python objects, not arrays):
#: passing one through jit without static_argnames is EG004
CONFIG_LIKE_PARAMS = frozenset({
    "cfg", "config", "mesh", "capacity", "codec", "codecs", "hop_codecs",
    "split", "split_cfg", "n_stages", "compute_dtype", "dtype", "temperature",
    "plan", "policy", "family",
})

#: callables that trace their function argument (make it jit-reachable)
TRACE_WRAPPERS = frozenset({
    "jit", "shard_map", "scan", "vmap", "pmap", "pjit", "checkpoint",
    "remat", "cond", "while_loop", "fori_loop", "switch", "grad",
    "value_and_grad", "custom_jvp", "custom_vjp", "eval_shape", "make_jaxpr",
})

#: host-I/O builtins / attribute paths flagged by EG002 inside traced code
HOST_IO_BUILTINS = frozenset({"print", "input", "open", "breakpoint"})
HOST_IO_MODULES = {
    "time": {"time", "monotonic", "perf_counter", "perf_counter_ns",
             "process_time", "sleep", "time_ns"},
    "subprocess": None,  # any attribute
    "os": {"system", "popen", "remove", "unlink", "makedirs", "mkdir"},
}

#: numpy namespaces whose math ops must not touch tracers (EG003); pure
#: metadata helpers are exempt below
NUMPY_ALIASES = frozenset({"np", "numpy", "onp"})
NUMPY_METADATA_FNS = frozenset({
    "dtype", "shape", "ndim", "issubdtype", "result_type", "promote_types",
    "finfo", "iinfo", "can_cast", "prod",  # np.prod(shape) is host math
})

#: container-mutating method names for EG006
MUTATING_METHODS = frozenset({
    "append", "extend", "insert", "update", "add", "pop", "popitem",
    "remove", "clear", "setdefault", "discard",
})

#: EG007 vocabulary: registry factory methods, direct metric constructors,
#: and the span entry points whose first argument is THE name
METRIC_FACTORY_METHODS = frozenset({"counter", "gauge", "histogram"})
METRIC_CLASSES = frozenset({"Counter", "Gauge", "Histogram"})
SPAN_CALLEES = frozenset({"span", "obs_span", "phase", "obs_phase"})
SCOPE_CALLEES = frozenset({"named_scope"})

_DISABLE_RE = re.compile(r"#\s*graphlint:\s*disable(?:=([A-Z0-9, ]+))?")


# -- per-file analysis ------------------------------------------------------


class _FnInfo:
    """One function (or method / nested def) in the module."""

    __slots__ = ("node", "name", "params", "calls", "is_root", "static_names")

    def __init__(self, node: ast.AST, name: str) -> None:
        self.node = node
        self.name = name
        args = node.args
        self.params = [a.arg for a in
                       args.posonlyargs + args.args + args.kwonlyargs]
        self.calls: Set[str] = set()
        self.is_root = False
        self.static_names: Set[str] = set()


def _call_target_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute chains, 'jit' for Names, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jax_jit(node: ast.AST) -> bool:
    d = _dotted(node)
    return d in ("jax.jit", "jit", "pjit", "jax.pjit")


def _static_names_from_call(call: ast.Call) -> Optional[Set[str]]:
    """static_argnames from a jax.jit(...) call, or None when the value is
    not statically resolvable (a variable) — the check then stands down."""
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)):
            names = set()
            for elt in v.elts:
                if not (isinstance(elt, ast.Constant)
                        and isinstance(elt.value, str)):
                    return None
                names.add(elt.value)
            return names
        return None
    return set()


def _jit_wrapping_call(call: ast.Call) -> Optional[ast.Call]:
    """The jax.jit(...) call inside ``partial(jax.jit, ...)`` / plain jit."""
    if _is_jax_jit(call.func):
        return call
    if _dotted(call.func) in ("partial", "functools.partial") and call.args:
        if _is_jax_jit(call.args[0]):
            return call
    return None


class _ModuleIndex(ast.NodeVisitor):
    """Collect every function def, jit roots, and the by-name call graph."""

    def __init__(self) -> None:
        self.fns: List[_FnInfo] = []
        self.by_name: Dict[str, List[_FnInfo]] = {}
        self._stack: List[_FnInfo] = []
        #: Name -> static_argnames for `X = jax.jit(f, static_argnames=...)`
        self.wrapped_static: Dict[str, Optional[Set[str]]] = {}

    def _add(self, node) -> _FnInfo:
        info = _FnInfo(node, node.name)
        self.fns.append(info)
        self.by_name.setdefault(node.name, []).append(info)
        return info

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        info = self._add(node)
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _is_jax_jit(target):
                info.is_root = True
                if isinstance(dec, ast.Call):
                    jc = _jit_wrapping_call(dec)
                    if jc is not None:
                        info.static_names = _static_names_from_call(jc) or set()
            elif isinstance(dec, ast.Call):
                jc = _jit_wrapping_call(dec)
                if jc is not None:
                    info.is_root = True
                    info.static_names = _static_names_from_call(jc) or set()
        self._stack.append(info)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        if self._stack:
            tgt = _call_target_name(node.func)
            if tgt:
                self._stack[-1].calls.add(tgt)
        # fn passed to a tracing wrapper becomes a root: shard_map(body, ...)
        fname = _call_target_name(node.func)
        if fname in TRACE_WRAPPERS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    for info in self.by_name.get(arg.id, []):
                        info.is_root = True
            jc = _jit_wrapping_call(node) if fname in ("jit", "pjit") else None
            if jc is not None and jc.args and isinstance(jc.args[0], ast.Name):
                inner = jc.args[0].id
                self.wrapped_static[inner] = _static_names_from_call(jc)
                for info in self.by_name.get(inner, []):
                    info.is_root = True
                    info.static_names |= (self.wrapped_static[inner] or set())
        self.generic_visit(node)


def _reachable(index: _ModuleIndex) -> Set[int]:
    """Closure of jit roots over the by-simple-name call graph."""
    reach: Set[int] = set()
    frontier = [f for f in index.fns if f.is_root]
    while frontier:
        f = frontier.pop()
        if id(f) in reach:
            continue
        reach.add(id(f))
        for callee_name in f.calls:
            for callee in index.by_name.get(callee_name, []):
                if id(callee) not in reach:
                    frontier.append(callee)
        # nested defs trace when called from the traced body
        for sub in ast.walk(f.node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and sub is not f.node:
                for info in index.by_name.get(sub.name, []):
                    if info.node is sub and id(info) not in reach:
                        frontier.append(info)
    return reach


# -- rule visitors ----------------------------------------------------------


def _test_looks_traced(test: ast.AST) -> bool:
    """EG001 trigger: the branch condition computes on arrays — a jnp/lax
    call, or .any()/.all() on something."""
    for sub in ast.walk(test):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            root = d.split(".", 1)[0]
            if root in ("jnp", "lax") or d.startswith("jax.numpy") \
                    or d.startswith("jax.lax"):
                return True
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in ("any", "all") \
                    and not isinstance(sub.func.value, ast.Call):
                return True
    return False


def _host_io_call(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name) and f.id in HOST_IO_BUILTINS:
        return f.id
    d = _dotted(f)
    if "." in d:
        mod, attr = d.split(".", 1)
        allowed = HOST_IO_MODULES.get(mod)
        if mod in HOST_IO_MODULES and (allowed is None or attr in allowed):
            return d
        if d in ("sys.stdout.write", "sys.stderr.write"):
            return d
    return None


def _numpy_math_call(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
            and f.value.id in NUMPY_ALIASES \
            and f.attr not in NUMPY_METADATA_FNS:
        return f"{f.value.id}.{f.attr}"
    return None


def _maybe_traced_names(info: _FnInfo) -> Set[str]:
    """Parameters plausibly holding tracers: everything except self/cls,
    declared-static names, and config-like python objects."""
    out = set()
    for p in info.params:
        if p in ("self", "cls"):
            continue
        if p in info.static_names or p in CONFIG_LIKE_PARAMS:
            continue
        out.add(p)
    return out


def _arg_touches(node: ast.AST, names: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return True
        if isinstance(sub, ast.Attribute):
            # x.shape / x.dtype are host metadata, not array math
            if sub.attr in ("shape", "dtype", "ndim", "size"):
                return False
    return False


def _local_names(fn: ast.AST) -> Set[str]:
    """Names bound inside ``fn`` (params, assignments, loop targets,
    comprehensions, nested defs) — everything NOT captured."""
    names: Set[str] = set()
    args = fn.args
    for a in args.posonlyargs + args.args + args.kwonlyargs:
        names.add(a.arg)
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            names.add(sub.id)
        elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and sub is not fn:
            names.add(sub.name)
        elif isinstance(sub, ast.comprehension):
            for t in ast.walk(sub.target):
                if isinstance(t, ast.Name):
                    names.add(t.id)
    return names


def _check_traced_fn(info: _FnInfo, path: str, emit) -> None:
    """EG001 / EG002 / EG003 / EG006 over one jit-reachable function."""
    traced = _maybe_traced_names(info)
    own_nested = [n for n in ast.walk(info.node)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and n is not info.node]
    nested_ids = {id(n) for n in own_nested}

    for node in ast.walk(info.node):
        # skip statements living inside nested defs for the branch rules —
        # the nested def is its own reachable unit
        if isinstance(node, (ast.If, ast.While)) \
                and _test_looks_traced(node.test):
            emit("EG001", node.lineno,
                 "Python branch on a traced value inside a jit-reachable "
                 "function; use lax.cond/jnp.where or hoist the check to "
                 "host code")
        elif isinstance(node, ast.Assert) and _test_looks_traced(node.test):
            emit("EG001", node.lineno,
                 "assert on a traced value inside a jit-reachable function; "
                 "it evaluates once at trace time — use "
                 "checkify or a host-side check")
        elif isinstance(node, ast.Call):
            io = _host_io_call(node)
            if io is not None:
                emit("EG002", node.lineno,
                     f"host I/O `{io}(...)` reachable from a jitted "
                     f"function; it runs at trace time, not per call — "
                     f"use jax.debug.print or move it to host code")
            npcall = _numpy_math_call(node)
            if npcall is not None and any(
                    _arg_touches(a, traced) for a in node.args):
                emit("EG003", node.lineno,
                     f"`{npcall}` applied to a likely-traced array; numpy "
                     f"forces a host transfer under trace — use the jnp "
                     f"equivalent")

    # EG006: nested defs mutating captured containers
    for nested in own_nested:
        locals_ = _local_names(nested)
        for node in ast.walk(nested):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in MUTATING_METHODS \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id not in locals_:
                emit("EG006", node.lineno,
                     f"`{node.func.value.id}.{node.func.attr}(...)` mutates "
                     f"a container captured from the enclosing scope inside "
                     f"traced code; the mutation happens once at trace time "
                     f"— return the value instead")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id not in locals_:
                        emit("EG006", t.lineno,
                             f"subscript assignment into captured "
                             f"`{t.value.id}` inside traced code; the write "
                             f"happens once at trace time")
    _ = nested_ids  # (kept for clarity of intent above)


def _check_jit_static(index: _ModuleIndex, tree: ast.Module, emit) -> None:
    """EG004 over every jax.jit site whose wrapped signature is resolvable."""

    def check(params: List[str], static: Optional[Set[str]], line: int,
              fname: str) -> None:
        if static is None:  # static_argnames not statically resolvable
            return
        missing = [p for p in params
                   if p in CONFIG_LIKE_PARAMS and p not in static]
        if missing:
            emit("EG004", line,
                 f"jax.jit on `{fname}` takes config-like parameter(s) "
                 f"{missing} not listed in static_argnames; each distinct "
                 f"config will fail to trace or silently retrace")

    for info in index.fns:
        node = info.node
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _is_jax_jit(target):
                static = (_static_names_from_call(_jit_wrapping_call(dec))
                          if isinstance(dec, ast.Call) else set())
                check(info.params, static, node.lineno, info.name)
            elif isinstance(dec, ast.Call):
                jc = _jit_wrapping_call(dec)
                if jc is not None:
                    check(info.params, _static_names_from_call(jc),
                          node.lineno, info.name)

    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        jc = _jit_wrapping_call(call)
        if jc is None or not jc.args or not isinstance(jc.args[0], ast.Name):
            continue
        inner = jc.args[0].id
        for info in index.by_name.get(inner, []):
            check(info.params, _static_names_from_call(jc), call.lineno,
                  inner)
            break  # one resolution is enough


def _is_host_numpy_expr(node: ast.AST) -> bool:
    """True when the expression is plain-numpy host math (np.prod(shape) in a
    checkpoint parser, say) — coercing THAT to int is not a device sync."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d.split(".", 1)[0] in NUMPY_ALIASES:
                return True
    return False


def _check_decode_loops(index: _ModuleIndex, path: str, emit) -> None:
    """EG005: per-token host syncs inside decode/generate loops."""
    in_serve = f"{os.sep}serve{os.sep}" in path
    for info in index.fns:
        name_l = info.name.lower()
        if not (in_serve or "generate" in name_l or "decode" in name_l):
            continue
        for loop in ast.walk(info.node):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if isinstance(f, ast.Attribute) and f.attr == "item" \
                        and not node.args:
                    emit("EG005", node.lineno,
                         "`.item()` inside a decode loop forces a device "
                         "sync per token; accumulate on device and sync "
                         "once after the loop")
                elif _dotted(f) in ("jax.device_get", "device_get"):
                    emit("EG005", node.lineno,
                         "`jax.device_get` inside a decode loop forces a "
                         "device sync per token; sync once after the loop")
                elif isinstance(f, ast.Name) and f.id in ("float", "int") \
                        and node.args \
                        and isinstance(node.args[0],
                                       (ast.Call, ast.Subscript)) \
                        and not _is_host_numpy_expr(node.args[0]):
                    emit("EG005", node.lineno,
                         f"`{f.id}(...)` of a computed value inside a "
                         f"decode loop is a per-token host sync; keep the "
                         f"value on device")


def _literal_name_pattern(node: ast.AST) -> Optional[str]:
    """The statically-known name of a metric/span call's first argument:
    a string constant verbatim, an f-string with its holes as ``*``, or
    None (dynamic — EG007 stands down)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for piece in node.values:
            if isinstance(piece, ast.Constant) and isinstance(piece.value,
                                                              str):
                parts.append(piece.value)
            else:
                parts.append("*")
        return "".join(parts)
    return None


def _check_registered_names(tree: ast.Module, emit) -> None:
    """EG007 over every metric/span call site with a literal name."""
    try:
        from ..obs import names as obs_names
    except ImportError:  # pragma: no cover - standalone lint of one file
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        is_metric = (
            (isinstance(f, ast.Attribute)
             and f.attr in METRIC_FACTORY_METHODS)
            or (isinstance(f, ast.Name) and f.id in METRIC_CLASSES))
        is_span = ((isinstance(f, ast.Name) and f.id in SPAN_CALLEES)
                   or (isinstance(f, ast.Attribute)
                       and f.attr in SPAN_CALLEES))
        is_scope = _call_target_name(f) in SCOPE_CALLEES
        if not (is_metric or is_span or is_scope):
            continue
        pattern = _literal_name_pattern(node.args[0])
        if pattern is None:
            continue  # dynamic name: not statically checkable
        if is_metric and not obs_names.metric_registered(pattern):
            emit("EG007", node.lineno,
                 f"metric name {pattern!r} is not in the registered "
                 f"vocabulary (obs/names.py); register it there or fix "
                 f"the typo — an unregistered series is never scraped")
        elif is_span and not obs_names.span_registered(pattern):
            emit("EG007", node.lineno,
                 f"span name {pattern!r} is not in the registered "
                 f"vocabulary (obs/names.py); register it there or fix "
                 f"the typo")
        elif is_scope and not obs_names.scope_registered(pattern):
            emit("EG007", node.lineno,
                 f"named_scope {pattern!r} is not in SCOPE_NAMES "
                 f"(obs/names.py); trace readers sum device time by these "
                 f"names — register it there or fix the typo")


# -- driver -----------------------------------------------------------------


def _suppressed_lines(source: str) -> Dict[int, Optional[Set[str]]]:
    """line -> set of suppressed rule ids (None = all rules)."""
    out: Dict[int, Optional[Set[str]]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _DISABLE_RE.search(line)
        if m:
            rules = m.group(1)
            out[i] = ({r.strip() for r in rules.split(",") if r.strip()}
                      if rules else None)
    return out


def lint_source(source: str, path: str) -> List[Finding]:
    """All AST findings for one module's source text."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(layer="ast", rule="EG000", where=path,
                        line=e.lineno or 0,
                        message=f"syntax error: {e.msg}")]

    index = _ModuleIndex()
    index.visit(tree)
    reach = _reachable(index)
    suppressed = _suppressed_lines(source)
    raw: List[Tuple[str, int, str]] = []

    def emit(rule: str, line: int, message: str) -> None:
        sup = suppressed.get(line)
        if line in suppressed and (sup is None or rule in sup):
            return
        raw.append((rule, line, message))

    for info in index.fns:
        if id(info) in reach:
            _check_traced_fn(info, path, emit)
    _check_jit_static(index, tree, emit)
    _check_decode_loops(index, path, emit)
    _check_registered_names(tree, emit)

    seen: Set[Tuple[str, int, str]] = set()
    findings = []
    for rule, line, message in raw:
        key = (rule, line, message)
        if key in seen:
            continue
        seen.add(key)
        findings.append(Finding(layer="ast", rule=rule, where=path,
                                line=line, message=message))
    return findings


def lint_file(path: str) -> List[Finding]:
    with open(path, "r", encoding="utf-8") as f:
        return lint_source(f.read(), path)


def iter_package_files(root: str) -> Iterator[str]:
    """Every .py under ``root``, skipping caches and the lint pkg itself
    (its fixture-shaped docstrings and rule tables would self-trip)."""
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git", ".jax_cache")]
        if os.path.basename(dirpath) == "lint":
            dirnames[:] = []
            continue
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_paths(paths) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        out.extend(lint_file(p))
    return out


def collect_suppressions(paths) -> List[Tuple[str, int, Optional[Set[str]]]]:
    """Every ``# graphlint: disable=`` marker across ``paths``.

    Returns ``(path, line, rules)`` triples sorted by location; ``rules``
    is None for a bare ``disable`` (all rules) or the set of rule ids a
    comma-separated marker names. Feeds the CLI's ``--show-suppressed``
    audit so silenced lines stay reviewable instead of invisible.
    """
    out: List[Tuple[str, int, Optional[Set[str]]]] = []
    for p in paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                source = f.read()
        except OSError:
            continue
        for line, rules in sorted(_suppressed_lines(source).items()):
            out.append((p, line, rules))
    return sorted(out, key=lambda t: (t[0], t[1]))
