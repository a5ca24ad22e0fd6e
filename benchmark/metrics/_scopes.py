"""Device operations to their scope path: ``jax.named_scope`` names, flax
module names and JAX's own transform markers (``jvp``, ``transpose``,
``rematted_computation``), as XLA's ``op_name`` metadata has them.

The route this JAX offers (seen by hand on a TPU v5e trace, PR 26): the
trace file carries the scope path, not the event. Every ``XLA Ops`` event
points at an event-metadata record of its plane, and that record holds the
stat ``tf_op`` = ``<op_name>:<primitive>``, taken from the compiled
executable's HLO metadata. ``jax.profiler.ProfileData`` shows an event's
own stats only, not its metadata's, so the xplane's protobuf is read here
directly: a varint / length-delimited walk over the four messages needed
(XSpace.planes, XPlane.event_metadata and .stat_metadata, XEventMetadata
.name and .stats, XStat), skipping the event lines unread. The event's
name (the operation's HLO text) is the key, as in ``trace_reduce``, whose
events carry nothing else. A process holds many programs (the serve cell's
about 47), and one text may stand in two of them under different scopes:
such a name is AMBIGUOUS, gets no scope, and counts as unscoped below, so
that it shows instead of passing for another program's scope.

A fusion that spans scopes is one device operation with one ``op_name``:
XLA gives the fusion its root instruction's. So a fusion counts WHOLLY for
the scope of the operation that produces its result, and a producer fused
into a consumer of another scope is counted with the consumer: XLA's
fusion choices, not the scope, draw the line between two neighbouring
scopes, and a change of fusion moves time across it with no change of the
work. Operations without the stat (copies and slices the compiler adds)
and operations the compiler hoists out of a loop (named after the ``while``
alone) name no part of the model: they are UNSCOPED (``names_no_part``),
and a reader of one scope's share is read beside the unscoped share.

The names are the executable's: a program loaded from a persistent compile
cache keeps the names it was compiled under (JAX leaves them out of the
cache key), so an executable cached before a scope existed shows none.

``obs["op_scopes"]`` (event name -> scope path), where a test or a later
harness provides it, is taken first.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from benchmark import trace_reduce as tr
from benchmark.metrics import _spans

_loaded: Dict[Tuple[str, int], Tuple[Dict[str, str], List[str]]] = {}


# -- the protobuf wire format, as far as an xplane needs it --------------------

def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message held in bytes or a
    memoryview; a length-delimited value comes as a memoryview slice of
    it, uncopied and unread."""
    view = memoryview(buf)
    at, end = 0, len(view)
    while at < end:
        key, at = _varint(view, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(view, at)
        elif wire == 1:
            value, at = view[at:at + 8], at + 8
        elif wire == 2:
            size, at = _varint(view, at)
            value, at = view[at:at + size], at + size
        elif wire == 5:
            value, at = view[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield number, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_value(entry):
    """The value (field 2) of one protobuf map entry."""
    return next((v for n, w, v in _fields(entry) if n == 2 and w == 2), None)


def _plane_scopes(plane) -> List[Tuple[str, str]]:
    """(event-metadata name, op_name) of every record of one XPlane that
    has both; a name comes once per program that holds such an operation."""
    stat_names: Dict[int, str] = {}
    metadata = []
    for number, wire, value in _fields(plane):
        if wire != 2 or number not in (4, 5):
            continue
        record = _map_value(value)
        if record is None:
            continue
        if number == 4:                          # event_metadata map entry
            metadata.append(record)
        else:                                    # stat_metadata map entry
            ident, name = 0, ""
            for n, w, v in _fields(record):
                if n == 1 and w == 0:
                    ident = v
                elif n == 2 and w == 2:
                    name = _text(v)
            stat_names[ident] = name
    wanted = {i for i, n in stat_names.items() if n == "tf_op"}
    out: List[Tuple[str, str]] = []
    for record in metadata:
        name, scope = "", None
        for n, w, v in _fields(record):
            if n == 2 and w == 2:
                name = _text(v)
            elif n == 5 and w == 2:              # one XStat
                ident, text = None, None
                for sn, sw, sv in _fields(v):
                    if sn == 1 and sw == 0:
                        ident = sv
                    elif sn == 5 and sw == 2:    # str_value
                        text = _text(sv)
                    elif sn == 7 and sw == 0:    # ref_value -> a stat's name
                        text = stat_names.get(sv, "")
                if ident in wanted and text is not None:
                    scope = text
        if name and scope:
            out.append((name, scope.rsplit(":", 1)[0]))     # "<op_name>:<primitive>"
    return out


def _load(path: str) -> Tuple[Dict[str, str], List[str]]:
    key = (path, os.stat(path).st_mtime_ns)      # a rewritten file is another file
    if key not in _loaded:
        out: Dict[str, str] = {}
        ambiguous = set()
        with open(path, "rb") as f:
            space = f.read()
        for number, wire, plane in _fields(space):
            if number != 1 or wire != 2:
                continue
            name = next((_text(v) for n, w, v in _fields(plane) if n == 2 and w == 2), "")
            if name.startswith("/device:TPU:"):
                for op, scope in _plane_scopes(plane):
                    if out.setdefault(op, scope) != scope:
                        ambiguous.add(op)
        for op in ambiguous:
            del out[op]
        _loaded[key] = (out, sorted(ambiguous))
    return _loaded[key]


def scopes_in_file(path: str) -> Dict[str, str]:
    """Event name -> scope path over the device planes of an xplane file,
    without the ambiguous names."""
    return _load(path)[0]


def ambiguous_in_file(path: str) -> List[str]:
    """The event names that two programs of the file put under different
    scope paths."""
    return _load(path)[1]


def op_scopes(obs: Dict[str, Any]) -> Dict[str, str]:
    if "op_scopes" in obs:
        return obs["op_scopes"]
    path = _spans.xplane_of(obs)
    return scopes_in_file(path) if path else {}


# -- reductions ----------------------------------------------------------------

def has_scope(path: str, name: str) -> bool:
    """``name`` is a component of the scope path, bare or inside a
    transform's marker (``transpose(jvp(loss))``)."""
    return re.search(r"(?:^|[/(])" + re.escape(name) + r"(?:[/)]|$)", path) is not None


def op_seconds(obs: Dict[str, Any], within: Optional[List[tr.Event]] = None,
               ) -> Dict[str, float]:
    """Device seconds by event name, over all devices, of the operations
    wholly inside the traced window that are not containers (their bodies
    are listed themselves); with ``within``, only of those that ran inside
    one of these program executions."""
    lo, hi = obs["trace_window"]
    runs = sorted((m.start_ns, m.end_ns) for m in within) if within is not None else None
    starts = [s for s, _ in runs] if runs is not None else None
    acc: Dict[str, float] = {}
    for evs in obs["trace"].device_ops.values():
        for e in evs:
            if e.start_ns < lo or e.end_ns > hi:
                continue
            if runs is not None:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i < 0 or e.end_ns > runs[i][1]:
                    continue
            acc[e.name] = acc.get(e.name, 0.0) + e.dur_ns / 1e9
    return {name: s for name, s in acc.items() if not tr.is_container(name)}


#: the program and the control flow around the model's parts
_CONTAINER = re.compile(r"p?jit\(.*\)|while|body|cond|branch_\d+|closed_call")


def names_no_part(path: str) -> bool:
    """Unscoped: no ``op_name`` at all, or one made of the program and its
    control flow alone (``jit(step)/while``: what the compiler hoisted out
    of the scan, named after the loop itself)."""
    return all(_CONTAINER.fullmatch(part) for part in path.split("/") if part)


def seconds_where(obs: Dict[str, Any], wanted: Callable[[str], bool],
                  within: Optional[List[tr.Event]] = None) -> Optional[float]:
    """Device seconds of the operations whose scope path satisfies
    ``wanted``. None where the trace names no operation's scope at all."""
    scopes = op_scopes(obs)
    if not scopes:
        return None
    return sum(s for op, s in op_seconds(obs, within).items() if wanted(scopes.get(op, "")))


def under(*names: str) -> Callable[[str], bool]:
    return lambda path: any(has_scope(path, name) for name in names)


def share_of_program(obs: Dict[str, Any], wanted: Callable[[str], bool]) -> Optional[float]:
    """Percent of the device time of the serve cell's decode program
    (``obs["program_name"]``: its executions in the traced window) spent in
    operations whose scope path satisfies ``wanted``; None where none did."""
    if obs["kind"] != "serve" or "trace" not in obs:
        return None
    runs = tr.module_events(obs["trace"], obs["trace_window"], obs["program_name"])
    if not runs:
        return None
    seconds = seconds_where(obs, wanted, within=runs)
    if not seconds:
        return None
    return 100.0 * seconds / (sum(m.dur_ns for m in runs) / 1e9)


def share_of_busy(obs: Dict[str, Any], name: str) -> Optional[float]:
    """Percent of the devices' busy time in the traced window, summed over
    the chips, spent under the scope ``name``; None where nothing ran
    under it."""
    if obs["kind"] != "train" or "trace" not in obs:
        return None
    seconds = seconds_where(obs, under(name))
    busy = sum(obs["busy_by_device"].values())
    if not seconds or busy <= 0:
        return None
    return 100.0 * seconds / busy


def seconds_by_scope(obs: Dict[str, Any], names: Tuple[str, ...],
                     within: Optional[List[tr.Event]] = None,
                     ) -> Tuple[Dict[str, float], List[Tuple[str, float]]]:
    """For a breakdown: device seconds by the FIRST of ``names`` found in
    each operation's scope path, and the operations that fall under none
    of them (by printed name, with their scope path), largest first."""
    scopes = op_scopes(obs)
    acc: Dict[str, float] = {n: 0.0 for n in names}
    rest: Dict[str, float] = {}
    for op, s in op_seconds(obs, within).items():
        path = scopes.get(op, "")
        owner = next((n for n in names if has_scope(path, n)), None)
        if owner is None:
            key = f"{tr.short_name(op)} [{path or 'no scope'}]"
            rest[key] = rest.get(key, 0.0) + s
        else:
            acc[owner] += s
    return acc, sorted(rest.items(), key=lambda kv: -kv[1])
