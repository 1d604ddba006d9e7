"""Graph-contract registry and the jaxpr-level checkers behind it.

The stack's load-bearing invariants — "only quantized bytes cross the
boundary hop", "the hop plan adds exactly N collectives", "no f64, no host
callbacks, donated KV buffers", "a disabled feature builds the identical
graph" — were each proven ad hoc in one test and enforced nowhere else.
This module promotes them to *declared contracts*: a subsystem opts in by
decorating its entry point with :func:`graph_contract`, and the lint CLI
traces the real function (``jax.make_jaxpr`` / ``.lower()``) and verifies
the declaration against the actual graph.

Contract fields may be plain values or callables taking a ``ctx`` dict —
the driver (``lint.entrypoints``) supplies measured facts (payload leaf
counts, hop byte totals) so a declaration like ``collectives=lambda ctx:
{"ppermute": ctx["n_hops"] * ctx["payload_leaves"], "psum": 1}`` states the
*invariant* while the numbers come from the codec registry, not from a
hand-maintained constant that rots.

Checkers are pure jaxpr/HLO walks — nothing here executes model code.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import Counter
from typing import Any, Callable, Iterator, Mapping, Optional, Union

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

from .report import Finding

#: communication primitives counted by the collective-count contract;
#: a silently-added collective is exactly what this check exists to catch
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "pgather",
})

#: primitives that re-enter the host from inside a jitted graph — forbidden
#: on every decode/forward hot path (each one is a device->host sync)
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "host_callback", "infeed", "outfeed",
})

#: dtypes the "no f64" contract rejects: double precision anywhere in a
#: traced graph means a silent promotion slipped in (TPUs emulate f64 at a
#: catastrophic slowdown; the paper's wire formats are int4/int8/bf16)
F64_DTYPES = frozenset({"float64", "complex128"})

_DEFAULT_FORBID = ("f64", "host_callback")


@dataclasses.dataclass(frozen=True)
class GraphContract:
    """A declared graph-level contract for one traced entry point.

    Every field except ``name``/``fn`` may be a plain value or a
    ``callable(ctx) -> value`` resolved at check time (see module
    docstring). ``None`` disables that particular check.

    collectives: exact {primitive name: count} over the whole traced graph
        (scan/shard_map bodies count once — these are static graph counts).
    wire_dtypes: allowed dtype names for every operand of every ``ppermute``
        (the boundary-hop wire). Anything else crossing a cut is a leak.
    wire_bytes: exact total payload bytes moved by all ``ppermute`` eqns.
    forbid: subset of {"f64", "host_callback"}.
    donate: minimum number of donated (input->output aliased) buffers the
        *lowered* entry point must carry — 0 disables the check.
    """

    name: str
    fn: Optional[Callable] = None
    collectives: Union[None, Mapping[str, int], Callable] = None
    wire_dtypes: Union[None, frozenset, Callable] = None
    wire_bytes: Union[None, int, Callable] = None
    forbid: tuple = _DEFAULT_FORBID
    donate: Union[int, Callable] = 0

    def resolve(self, field: str, ctx: Optional[dict]) -> Any:
        val = getattr(self, field)
        return val(ctx or {}) if callable(val) else val


#: the in-code registry ``@graph_contract`` populates; the lint CLI's graph
#: layer iterates it (drivers in ``lint.entrypoints`` know how to build
#: example inputs for each name)
GRAPH_CONTRACTS: dict = {}


def graph_contract(name: Optional[str] = None, *,
                   collectives: Union[None, Mapping[str, int], Callable] = None,
                   wire_dtypes: Union[None, frozenset, Callable] = None,
                   wire_bytes: Union[None, int, Callable] = None,
                   forbid: tuple = _DEFAULT_FORBID,
                   donate: Union[int, Callable] = 0) -> Callable:
    """Declare a graph contract on an entry point (decorator, zero runtime
    cost — it only records the spec and returns the function unchanged).

    Usage::

        @graph_contract("split.forward",
                        collectives=lambda ctx: {"ppermute": ctx["hop_eqns"],
                                                 "psum": 1},
                        wire_bytes=lambda ctx: ctx["wire_bytes"])
        def forward(self, ...): ...

    A new subsystem opts in with one decorator plus a driver in
    ``lint.entrypoints`` that builds example inputs (see REPRODUCING §8).
    """
    unknown = set(forbid) - {"f64", "host_callback"}
    if unknown:
        raise ValueError(f"unknown forbid entries {sorted(unknown)}; "
                         f"supported: 'f64', 'host_callback'")

    def deco(fn: Callable) -> Callable:
        cname = name or fn.__qualname__
        GRAPH_CONTRACTS[cname] = GraphContract(
            name=cname, fn=fn, collectives=collectives,
            wire_dtypes=wire_dtypes, wire_bytes=wire_bytes, forbid=forbid,
            donate=donate)
        fn.__graph_contract__ = cname  # type: ignore[attr-defined]
        return fn

    return deco


# ---------------------------------------------------------------------------
# jaxpr walks
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: Mapping) -> Iterator:
    """Yield every Jaxpr/ClosedJaxpr nested in an equation's params
    (pjit/scan/while/cond/shard_map/custom_* all stash theirs differently)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every equation of a (Closed)Jaxpr, including all
    nested sub-jaxprs. Bodies of scan/shard_map are visited ONCE — contract
    counts are static graph counts, not runtime trip counts."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def count_collectives(jaxpr) -> Counter:
    """Static {collective primitive: equation count} over the whole graph."""
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            c[eqn.primitive.name] += 1
    return c


def ppermute_traffic(jaxpr) -> list:
    """[(dtype name, shape, nbytes)] for every ``ppermute`` operand — the
    bytes that actually cross a boundary hop, read off the traced graph."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "ppermute":
            continue
        for v in eqn.invars:
            aval = v.aval
            nbytes = int(aval.size) * aval.dtype.itemsize
            out.append((aval.dtype.name, tuple(aval.shape), nbytes))
    return out


def _all_avals(jaxpr) -> Iterator:
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for v in list(jaxpr.invars) + list(jaxpr.constvars) + list(jaxpr.outvars):
        yield v.aval
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            yield v.aval


def find_f64(jaxpr) -> list:
    """Dtype-name list of every f64/c128 aval anywhere in the graph."""
    hits = []
    for aval in _all_avals(jaxpr):
        dt = getattr(aval, "dtype", None)
        if dt is not None and dt.name in F64_DTYPES:
            hits.append(dt.name)
    return hits


def find_callbacks(jaxpr) -> list:
    """Primitive names of every host re-entry inside the graph."""
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in CALLBACK_PRIMS]


def donated_input_count(jitted_fn: Callable, *args: Any, **kwargs: Any) -> int:
    """Number of input buffers the entry point donates — the static form of
    "the KV cache is updated in place, not copied every step".

    Counted two ways and reconciled with max(): ``donated_invars`` on the
    traced pjit equation (the jit-level declaration, robust on every
    backend), and ``tf.aliasing_output`` annotations in the lowered
    StableHLO (present where the backend actually implements aliasing —
    single-device paths here; the multi-device CPU grid drops them even
    though the declaration stands)."""
    declared = 0
    try:
        jaxpr = jax.make_jaxpr(jitted_fn)(*args, **kwargs)
        for eqn in jaxpr.jaxpr.eqns:
            di = eqn.params.get("donated_invars")
            if di:
                declared += sum(1 for d in di if d)
    except Exception:  # noqa: BLE001 — fall through to the lowering count
        pass
    lowered = jitted_fn.lower(*args, **kwargs)
    return max(declared, lowered.as_text().count("tf.aliasing_output"))


def graph_fingerprint(fn: Callable, *args: Any, **kwargs: Any) -> str:
    """sha256 over the pretty-printed jaxpr of ``fn(*args)`` — two builds
    with the same fingerprint compile the same graph. This is PR 2/3's
    "disabled config is bit-identical to the pre-feature graph" test turned
    into a reusable checker."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return hashlib.sha256(jaxpr.pretty_print().encode()).hexdigest()


# ---------------------------------------------------------------------------
# the contract checker
# ---------------------------------------------------------------------------


def check_traced(contract: GraphContract, traced_fn: Callable, args: tuple,
                 ctx: Optional[dict] = None,
                 lowerable: Optional[Callable] = None,
                 lower_args: Optional[tuple] = None) -> list:
    """Verify one contract against the real traced graph.

    ``traced_fn``/``args`` build the jaxpr (the driver's example inputs);
    ``lowerable``/``lower_args``, when given, are the *jitted* entry point
    the donation check lowers. Returns a list of :class:`Finding` (empty =
    contract holds)."""
    findings = []

    def fail(rule: str, msg: str) -> None:
        findings.append(Finding(layer="graph", rule=rule, where=contract.name,
                                line=0, message=msg))

    try:
        jaxpr = jax.make_jaxpr(traced_fn)(*args)
    except Exception as e:  # noqa: BLE001 — a contract that cannot trace IS a finding
        fail("GC-trace", f"entry point failed to trace: {type(e).__name__}: {e}")
        return findings

    if "f64" in contract.forbid:
        hits = find_f64(jaxpr)
        if hits:
            fail("GC-f64",
                 f"{len(hits)} double-precision aval(s) in the traced graph "
                 f"({sorted(set(hits))}); a silent f32->f64 promotion slipped "
                 f"into the jitted path")
    if "host_callback" in contract.forbid:
        cbs = find_callbacks(jaxpr)
        if cbs:
            fail("GC-callback",
                 f"host callback(s) {sorted(set(cbs))} inside the jitted "
                 f"graph; each one is a device->host sync on the hot path")

    want = contract.resolve("collectives", ctx)
    if want is not None:
        got = count_collectives(jaxpr)
        want_c = Counter({k: v for k, v in dict(want).items() if v})
        if got != want_c:
            fail("GC-collectives",
                 f"collective count mismatch: declared {dict(want_c)}, traced "
                 f"graph has {dict(got)} — a collective was silently added or "
                 f"removed")

    dtypes = contract.resolve("wire_dtypes", ctx)
    nbytes = contract.resolve("wire_bytes", ctx)
    if dtypes is not None or nbytes is not None:
        traffic = ppermute_traffic(jaxpr)
        if dtypes is not None:
            allowed = frozenset(dtypes)
            bad = sorted({d for d, _, _ in traffic} - allowed)
            if bad:
                fail("GC-wire-dtype",
                     f"dtypes {bad} cross the boundary hop but the codec's "
                     f"declared wire format is {sorted(allowed)} — "
                     f"unquantized data is leaking across the cut")
        if nbytes is not None:
            total = sum(b for _, _, b in traffic)
            if total != int(nbytes):
                fail("GC-wire-bytes",
                     f"boundary hops move {total} bytes, codec declares "
                     f"{int(nbytes)} — payload width drifted from the wire "
                     f"contract")

    min_donate = contract.resolve("donate", ctx) or 0
    if min_donate:
        target = lowerable if lowerable is not None else traced_fn
        targs = lower_args if lower_args is not None else args
        try:
            n = donated_input_count(target, *targs)
        except Exception as e:  # noqa: BLE001
            fail("GC-donate", f"donation check failed to lower: "
                              f"{type(e).__name__}: {e}")
        else:
            if n < int(min_donate):
                fail("GC-donate",
                     f"only {n} input buffer(s) are donated "
                     f"(input->output aliased) in the lowered executable, "
                     f"contract requires >= {int(min_donate)} — the KV cache "
                     f"is being copied every step instead of updated in "
                     f"place")
    return findings


def check_identity(name: str, fn_a: Callable, args_a: tuple,
                   fn_b: Callable, args_b: tuple,
                   what: str = "disabled-config graph") -> list:
    """The reusable disabled-config-identity checker: both builds must hash
    to the identical jaxpr. Returns [] or one Finding."""
    try:
        fp_a = graph_fingerprint(fn_a, *args_a)
        fp_b = graph_fingerprint(fn_b, *args_b)
    except Exception as e:  # noqa: BLE001
        return [Finding(layer="graph", rule="GC-identity", where=name, line=0,
                        message=f"identity check failed to trace: "
                                f"{type(e).__name__}: {e}")]
    if fp_a != fp_b:
        return [Finding(
            layer="graph", rule="GC-identity", where=name, line=0,
            message=f"{what} is NOT identical to the pre-feature graph "
                    f"({fp_a[:12]} != {fp_b[:12]}); the disabled feature "
                    f"leaks machinery into the compiled executable")]
    return []
