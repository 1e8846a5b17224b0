"""Named device scopes of the HyTM engine, and the map from compiled
ops to them.

A scope is a ``jax.named_scope`` around one layer's traced code.  It
changes only the ``op_name`` metadata of the ops traced inside it, never
the compiled ops, so scopes are always on.  ``SCOPES`` lists every scope
of the iteration with the scope it nests in; ``OUTSIDE_SCOPES`` lists
those of programs outside the iteration, each at the top of its program:

=================  ====================================================
``select``         partition stats, Algorithm 1 (or the forced plan),
                   the Δ mass, the priority schedule, the diagnostics
``sweep``          each pass's scan over the partitions and its switch
``sweep.block``    a visit's block slices, ``frontier[src]``, operand
``engine.*``       one engine's whole relax (``filter``, ``compact``,
                   ``zerocopy``); ``NONE`` partitions clip to FILTER,
                   so ``engine.filter`` holds their skipped visits too
``filter.order``   the per-call route of ``segment_spmm``: its sort,
                   ``searchsorted``, the ``[order]`` gathers and pads, all
                   but its Pallas call.  Only blocks that come unrouted
                   reach it (a ``DeltaCSR`` view, the sharded sweep); a
                   runtime from ``build_runtime`` routes its partitions
                   once, and its programs have no ``filter.order``
``sweep.combine``  a visit's n-wide value, Δ and ``activated`` update
``update``         the next frontier, the info rows, the history writes
``stream.patch``   ``DeltaCSR``'s scatter of a batch's touched lanes
                   into the device edge arrays (outside the iteration)
=================  ====================================================

A device trace names an op by its HLO instruction alone.  So the map
from instruction to scope comes from the compiled program: ``run_hytm``
and ``DeltaCSR``'s patch register the abstract signature of each
program they compile
(:func:`register`: shapes, dtypes, shardings and statics, no arrays),
and :func:`op_scopes` lowers and compiles those signatures when asked,
which the in-memory or persistent compile cache answers, and reads
each instruction's innermost scope from its metadata.  Nothing runs
unless a reader asks.
"""

from __future__ import annotations

import dataclasses
import re
from collections.abc import Callable, Iterable

import jax

SELECT = "select"
SWEEP = "sweep"
SWEEP_BLOCK = "sweep.block"
SWEEP_COMBINE = "sweep.combine"
ENGINE_FILTER = "engine.filter"
ENGINE_COMPACT = "engine.compact"
ENGINE_ZEROCOPY = "engine.zerocopy"
FILTER_ORDER = "filter.order"
UPDATE = "update"
STREAM_PATCH = "stream.patch"

# scope -> the scope it nests in (None: directly in the iteration)
SCOPES: dict[str, str | None] = {
    SELECT: None,
    SWEEP: None,
    SWEEP_BLOCK: SWEEP,
    ENGINE_FILTER: SWEEP,
    ENGINE_COMPACT: SWEEP,
    ENGINE_ZEROCOPY: SWEEP,
    FILTER_ORDER: ENGINE_FILTER,
    SWEEP_COMBINE: SWEEP,
    UPDATE: None,
}
ENGINE_SCOPES = (ENGINE_FILTER, ENGINE_COMPACT, ENGINE_ZEROCOPY)
OUTSIDE_SCOPES = (STREAM_PATCH,)
# every scope -> the scope it nests in
_NESTS_IN = {**SCOPES, **dict.fromkeys(OUTSIDE_SCOPES)}


def scope(name: str):
    """``jax.named_scope(name)`` for one of ``SCOPES`` or
    ``OUTSIDE_SCOPES``."""
    if name not in _NESTS_IN:
        raise KeyError(f"{name!r} is not one of {sorted(_NESTS_IN)}")
    return jax.named_scope(name)


def within(name: str | None, outer: str) -> bool:
    """True if scope ``name`` is ``outer`` or nests inside it."""
    while name is not None:
        if name == outer:
            return True
        name = _NESTS_IN[name]
    return False


# -------------------------------------------------------- op -> scope map

# signature -> (jitted function, abstract arguments), in registration order
_PROGRAMS: dict = {}
# signature -> [(instruction line, innermost scope)], once compiled
_MAPS: dict = {}


def _abstract(x):
    if isinstance(x, jax.Array):
        # an uncommitted array lowers with no sharding annotation; giving
        # it one would compile (and cache) a program the run never ran
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None,
            weak_type=x.weak_type)
    return x


def register(signature, fn, *args) -> None:
    """Keep the abstract form of ``fn(*args)``, a jitted program that has
    just been dispatched for the first time, under ``signature``."""
    if signature not in _PROGRAMS:
        _PROGRAMS[signature] = (fn, jax.tree.map(_abstract, args))


def op_scopes() -> list[tuple[str, str | None]]:
    """Every instruction the registered programs run on the device, as
    its HLO line (without metadata or backend config) and its innermost
    scope (None outside every scope)."""
    out = []
    for signature, (fn, args) in list(_PROGRAMS.items()):
        if signature not in _MAPS:
            _MAPS[signature] = instruction_scopes(
                fn.lower(*args).compile().as_text())
        out += _MAPS[signature]
    return out


def scope_times(
    timed_ops: Iterable[tuple[str, float]], label: Callable[[str], str],
) -> dict[str | None, float]:
    """Sum ``(op name, time)`` pairs by the op's innermost scope, where
    ``label`` turns an HLO line of :func:`op_scopes` into an op name.
    An op the registered programs do not hold counts under None.  Empty
    when no program is registered."""
    scope_of = {label(line): s for line, s in op_scopes()}
    if not scope_of:
        return {}
    out: dict[str | None, float] = {}
    for name, t in timed_ops:
        s = scope_of.get(name)
        out[s] = out.get(s, 0.0) + t
    return out


_HEADER = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%(\S+) = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.-]+)")
_OPERAND = re.compile(r"%([\w.-]+)")
_TAIL = re.compile(r", (?:metadata|backend_config|custom_call_target)=")


def innermost(op_name: str) -> str | None:
    """The last component of an ``op_name`` path that is a scope."""
    for part in reversed(op_name.split("/")):
        if part in _NESTS_IN:
            return part
    return None


@dataclasses.dataclass
class _Instruction:
    line: str                # the HLO text up to metadata and configs
    name: str
    scope: str | None
    fuses: str | None        # a fusion's fused computation
    root: bool
    operands: set[str]


def instruction_scopes(hlo_text: str) -> list[tuple[str, str | None]]:
    """Each instruction of a compiled module's text outside fused
    computations (those are what a device trace shows), with its
    innermost scope.

    The compiler drops the metadata of some ops it makes or moves.  Such
    a fusion takes the scope of its fused root, else of its first fused
    op that names one; any other op without a scope (a hoisted constant
    broadcast, say) takes that of its first user that has one.  Loop
    plumbing whose only users are tuples keeps none."""
    computations: dict[str, list[_Instruction]] = {}
    body = None
    for line in hlo_text.splitlines():
        head = _HEADER.match(line)
        if head:
            body = computations.setdefault(head.group(1), [])
            continue
        inst = _INSTRUCTION.match(line)
        if inst and body is not None:
            op_name = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            text = _TAIL.split(inst.group(1), maxsplit=1)[0]
            body.append(_Instruction(
                text, inst.group(2),
                innermost(op_name.group(1)) if op_name else None,
                calls.group(1) if calls and " fusion(" in line else None,
                line.lstrip().startswith("ROOT "),
                set(_OPERAND.findall(text.split(" = ", 1)[1])),
            ))
    fused = {i.fuses for body in computations.values() for i in body if i.fuses}

    def fused_scope(name):
        body = computations.get(name, [])
        ranked = [i for i in body if i.root] + [i for i in body if not i.root]
        return next((i.scope for i in ranked if i.scope is not None), None)

    out = []
    for name, body in computations.items():
        if name in fused:
            continue
        users: dict[str, list[_Instruction]] = {}
        for i in body:
            if i.scope is None and i.fuses is not None:
                i.scope = fused_scope(i.fuses)
            for operand in i.operands:
                users.setdefault(operand, []).append(i)
        # users follow their operands, so one backward pass reaches the
        # first scoped user through chains of unscoped ones
        for i in reversed(body):
            if i.scope is None:
                i.scope = next((u.scope for u in users.get(i.name, ())
                                if u.scope is not None), None)
        out += [(i.line, i.scope) for i in body]
    return out
