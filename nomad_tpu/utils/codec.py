"""Generic dataclass <-> plain-dict codec used for JSON/msgpack wire
formats and state persistence (reference: nomad/structs/structs.generated.go
msgpack codegen; we derive codecs from dataclass type hints instead)."""

from __future__ import annotations

import dataclasses
import enum
import types
import typing
from itertools import repeat
from operator import attrgetter, is_
from typing import Any, List, Optional, get_args, get_origin, get_type_hints

_HINTS_CACHE: dict = {}


def _hints_for(cls: type) -> dict:
    """A dataclass's resolved field types, worked out once per class."""
    hints = _HINTS_CACHE.get(cls)
    if hints is None:
        hints = _HINTS_CACHE[cls] = get_type_hints(cls)
    return hints

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
    instances walked, `shared` the subtrees reused; `rows`, `consts`
    and `table` what rows_to_wire wrote under this memo: instances as
    rows, fields written once for a whole list, distinct objects
    tabled."""

    __slots__ = ("objects", "shared", "rows", "consts", "table")

    def __init__(self):
        super().__init__()
        self.objects = 0
        self.shared = 0
        self.rows = 0
        self.consts = 0
        self.table = 0


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


# atoms that `==` tells apart exactly within one type (a float does not:
# 0.0 == -0.0)
_EXACT = frozenset((str, int, bool, type(None)))


def rows_to_wire(objs: list, memo: Optional[ShareMemo] = None) -> dict:
    """A list of instances of ONE dataclass as one record instead of a
    list of dicts: a field that holds the same object, or an equal
    atom, on every instance is written once (`consts`); a field that
    varies over atoms is a column (`cols`); a field that varies over
    objects is a column of indices (`refs`, None where the instance
    holds None) into `table`, the distinct objects by identity, each
    walked once. Every value of every instance is in the record:
    rows_from_wire needs nothing else. With a memo the subtrees are
    shared as to_wire shares them, and the memo counts what was
    written."""
    n = len(objs)
    consts: dict = {}
    cols: dict = {}
    refs: dict = {}
    table: list = []
    out = {"rows": n, "consts": consts, "cols": cols, "refs": refs,
           "table": table}
    if not n:
        return out
    t = type(objs[0])
    _kind, names = _PLANS.get(t) or _plan_for(t)
    seen: dict = {}         # id(object) -> its place in the table
    for name in names:
        col = list(map(attrgetter(name), objs))
        first = col[0]
        # None: one object under every instance
        kinds = None if all(map(is_, col, repeat(first))) \
            else set(map(type, col))
        if kinds is None or (len(kinds) == 1 and type(first) in _EXACT
                             and col.count(first) == n):
            consts[name] = first if type(first) in _ATOMS \
                else to_wire(first, memo)
        elif kinds <= _ATOMS:
            cols[name] = col
        else:
            # `col` keeps every value alive, so an id names one object
            idx = refs[name] = []
            for v in col:
                if v is None:
                    idx.append(None)
                    continue
                k = seen.get(id(v))
                if k is None:
                    k = seen[id(v)] = len(table)
                    table.append(v if type(v) in _ATOMS
                                 else to_wire(v, memo))
                idx.append(k)
    if memo is not None:
        memo.rows += n
        memo.consts += len(consts)
        memo.table += len(table)
    return out


def rows_from_wire(cls: Any, data: dict) -> list:
    """The instances a rows_to_wire record stands for. Each constant
    and each table entry is built ONCE and hangs off every row that
    held it, by reference: the aliasing the encoded list had. A field
    the record lacks takes the class's default, a key the class lacks
    is passed over, as from_wire does."""
    hints = _hints_for(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    base = {k: from_wire(hints.get(k, Any), v)
            for k, v in data["consts"].items() if k in names}
    rows: List[dict] = [dict(base) for _ in range(data["rows"])]
    for name, col in data["cols"].items():
        if name not in names:
            continue
        hint = hints.get(name, Any)
        for kw, v in zip(rows, col):
            # an atom of the hinted type is its own decoded form
            kw[name] = v if type(v) is hint else from_wire(hint, v)
    table = data["table"]
    for name, idx in data["refs"].items():
        if name not in names:
            continue
        hint = hints.get(name, Any)
        built: dict = {}
        for kw, k in zip(rows, idx):
            if k is None:
                kw[name] = None
                continue
            if k not in built:
                built[k] = from_wire(hint, table[k])
            kw[name] = built[k]
    return [cls(**kw) for kw in rows]


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
        hints = _hints_for(cls)
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
