"""Generic dataclass <-> plain-dict codec used for JSON/msgpack wire
formats and state persistence (reference: nomad/structs/structs.generated.go
msgpack codegen; we derive codecs from dataclass type hints instead)."""

from __future__ import annotations

import dataclasses
import enum
import types
import typing
from typing import Any, Optional, get_args, get_origin, get_type_hints

_HINTS_CACHE: dict = {}

# values that are their own wire form, by exact type: the branch nearly
# every field of a model takes
_ATOMS = frozenset((str, int, float, bool, type(None)))
_SELF, _ENUM, _BYTES, _DATACLASS, _DICT, _SEQ = range(6)
# type -> (kind, field names): what to_wire does with an instance,
# worked out once per type instead of once per object
_PLANS: dict = {}


def _plan_for(t: type) -> tuple:
    """Classify a type in the order the wire form has always tested an
    instance in (bytes, then the scalars and their subclasses, enums,
    dataclasses, dicts, sequences; anything else rides through), and
    for a dataclass read its field names once."""
    names = None
    if issubclass(t, bytes):
        kind = _BYTES
    elif issubclass(t, (str, int, float, bool)):
        kind = _SELF
    elif issubclass(t, enum.Enum):
        kind = _ENUM
    elif dataclasses.is_dataclass(t):
        kind = _DATACLASS
        names = tuple(f.name for f in dataclasses.fields(t))
    elif issubclass(t, dict):
        kind = _DICT
    elif issubclass(t, (list, tuple, set, frozenset)):
        kind = _SEQ
    else:
        kind = _SELF
    plan = _PLANS[t] = (kind, names)
    return plan


class ShareMemo(dict):
    """One encoding's identity memo: id(obj) -> (obj, wire form), the
    object held so its id cannot be reused while the memo lives. An
    object reached twice is walked once and its wire form is reused BY
    REFERENCE, so the tree aliases wherever the objects did: only for
    a tree that is serialized and dropped, never edited (the WAL
    record, server/persistence.py). `objects` counts the dataclass
    instances walked, `shared` the subtrees reused."""

    __slots__ = ("objects", "shared")

    def __init__(self):
        super().__init__()
        self.objects = 0
        self.shared = 0


def to_wire(obj: Any, memo: Optional[ShareMemo] = None) -> Any:
    """Recursively convert dataclasses/enums/containers to plain data.
    bytes become tagged base64 dicts so the output is JSON-safe AND
    round-trips losslessly even inside Any-typed containers. Without a
    memo every dict and list of the result is new and the caller's to
    edit; with one see ShareMemo."""
    t = type(obj)
    if t in _ATOMS:
        return obj
    kind, names = _PLANS.get(t) or _plan_for(t)
    if kind == _SELF:
        return obj
    if kind == _ENUM:
        return obj.value
    if kind == _BYTES:
        import base64
        return {"__b64__": base64.b64encode(obj).decode("ascii")}
    if memo is not None:
        hit = memo.get(id(obj))
        if hit is not None:
            memo.shared += 1
            return hit[1]
    if kind == _DATACLASS:
        out = {}
        for name in names:
            v = getattr(obj, name)
            out[name] = v if type(v) in _ATOMS else to_wire(v, memo)
        if memo is not None:
            memo.objects += 1
    elif kind == _DICT:
        out = {k: v if type(v) in _ATOMS else to_wire(v, memo)
               for k, v in obj.items()}
    else:
        out = [v if type(v) in _ATOMS else to_wire(v, memo) for v in obj]
    if memo is not None:
        memo[id(obj)] = (obj, out)
    return out


def from_wire(cls: Any, data: Any) -> Any:
    """Recursively build an instance of `cls` from plain data."""
    if data is None:
        return None
    # tagged bytes decode regardless of the declared type, so bytes
    # survive Any-typed containers (e.g. Task.config values)
    if isinstance(data, dict) and len(data) == 1 and "__b64__" in data:
        import base64
        return base64.b64decode(data["__b64__"])
    origin = get_origin(cls)
    if origin in (typing.Union, types.UnionType):
        args = [a for a in get_args(cls) if a is not type(None)]
        if not args:
            return data
        return from_wire(args[0], data)
    if cls is Any or cls is None:
        return data
    if isinstance(cls, type) and issubclass(cls, enum.Enum):
        return cls(data)
    if dataclasses.is_dataclass(cls):
        hints = _HINTS_CACHE.get(cls)
        if hints is None:
            hints = get_type_hints(cls)
            _HINTS_CACHE[cls] = hints
        kwargs = {}
        names = {f.name for f in dataclasses.fields(cls)}
        for k, v in data.items():
            if k in names:
                kwargs[k] = from_wire(hints.get(k, Any), v)
        return cls(**kwargs)
    if cls in (list, tuple, set, frozenset):
        return cls(data)
    if cls is dict:
        return dict(data)
    if origin in (list, tuple, set, frozenset):
        args = get_args(cls)
        elem = args[0] if args else Any
        seq = [from_wire(elem, v) for v in data]
        if origin is list:
            return seq
        return origin(seq)
    if origin is dict:
        args = get_args(cls)
        vt = args[1] if len(args) == 2 else Any
        return {k: from_wire(vt, v) for k, v in data.items()}
    if cls is bytes:
        if isinstance(data, str):
            import base64
            return base64.b64decode(data)
        return bytes(data) if not isinstance(data, bytes) else data
    if cls in (int, float, str, bool):
        return cls(data) if data is not None else None
    return data
