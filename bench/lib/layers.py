"""Per-layer shares of a traced window, read from the names the program
gives its own work: the program's ``jax.profiler.TraceAnnotation`` spans
on the host, and the ``jax.named_scope`` path each device op carries in
its ``op_name`` metadata. A program without such a span or scope reads
None.

The host spans are in the reduced ``trace.Trace`` that every traced run
gets. The device ops' scopes are not: on the chip an op's ``op_name``
sits in its plane's event metadata (the ``tf_op`` stat), which
``jax.profiler.ProfileData`` does not expose, so ``op_scopes`` reads it
from the raw ``.xplane.pb`` (``bench/tools/layer_shares.py``).
"""
from __future__ import annotations

from typing import Dict, Tuple

from bench.lib import trace as tr


def host_share(run, span: str, minus=()):
    """The share (%) of the window the host spent inside spans named
    ``span``, less the time of the spans named in ``minus`` that run
    inside them on the same thread."""
    t = run["trace"]
    if t is None or "window_ns" not in run:
        return None
    lo, hi = run["window_ns"]
    outer = [(th, e) for th, e in t.host if e.name == span]
    if not outer:
        return None
    inner = [e for th, e in t.host if e.name in minus
             and any(th == th2 and o.start <= e.start and e.end <= o.end
                     for th2, o in outer)]
    spent = (tr.busy_ns([e for _, e in outer], lo, hi)
             - tr.busy_ns(inner, lo, hi))
    return 100.0 * spent / (hi - lo)


def device_share(run, scope: str, scopes: Dict[str, Dict[str, str]]):
    """The share (%) of the window the cell's chips spent in ops whose
    scope path (``scopes[plane][op name]``, from ``op_scopes``) has
    ``scope`` as a component, mean over the chips. Ops count by self
    time, so an enclosing ``while`` or ``conditional`` op is not counted
    again with the body it runs."""
    t = run["trace"]
    if t is None or not t.devices or "window_ns" not in run:
        return None
    lo, hi = run["window_ns"]
    planes = sorted(t.devices)[: run["chips"]]
    spent, found = 0.0, False
    for p in planes:
        named = scopes.get(p, {})
        for e, own in tr.self_times(tr.clip(t.devices[p], lo, hi)):
            if scope in named.get(e.name, "").split("/"):
                spent += own
                found = True
    if not found:
        return None
    return 100.0 * spent / len(planes) / (hi - lo)


# ------------------------------------------- op scopes of the raw trace
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``:
    a varint as an int, a length-delimited field as its (start, end)."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: unexpected wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    """The values (field 2) of a protobuf map's entries."""
    for entry in entries:
        value = dict(_fields(buf, *entry)).get(2)
        if value is not None:
            yield value


def op_scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """{TPU plane: {op event name: its op_name path}}, from the ``tf_op``
    stat (``op_name:op_type``) of each op's event metadata in the raw
    ``XSpace``. ``ProfileData`` gives an event's own stats only, and a
    TPU op's are its offset and duration; its HLO metadata sits in the
    plane's event metadata, under the same name. Fields, from the
    profiler's ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5 (maps: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2;
    XStat.metadata_id 1, .str_value 5, .ref_value 7."""
    buf = memoryview(data)
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        parts: Dict[int, list] = {2: [], 4: [], 5: []}
        for g, v in _fields(buf, *plane):
            if g in parts:
                parts[g].append(v)
        name = _text(buf, parts[2][0]) if parts[2] else ""
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for sm in _map_values(buf, parts[5]):
            sm = dict(_fields(buf, *sm))
            stat_names[sm.get(1, 0)] = _text(buf, sm[2]) if 2 in sm else ""
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        scopes = out.setdefault(name, {})
        for em in _map_values(buf, parts[4]):
            ev_name = op = ""
            for h, v in _fields(buf, *em):
                if h == 2:
                    ev_name = _text(buf, v)
                elif h == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1) in tf_op:
                        op = (_text(buf, st[5]) if 5 in st
                              else stat_names.get(st.get(7), ""))
            if op:
                scopes[ev_name] = op.rpartition(":")[0] if ":" in op else op
    return out
