"""Tagged-JSON codec for persisting/transporting framework types.

The reference serializes everything with generated protobuf
(proto/tendermint/*, 34k LoC). This framework keeps consensus-critical
byte strings hand-encoded (types/proto.py — those must be byte-exact) and
uses this self-describing JSON codec for storage records and non-canonical
wire payloads, where only round-trip fidelity matters.

Encoding rules: dataclasses carry a ``__t`` class tag; bytes are hex under
``__b``; IntEnums are ints (re-coerced from the declared field type on
decode); adapters cover non-dataclass types (key objects, ValidatorSet).

Encoding goes through a plan per type: the first value of a type decides
which rule it takes, and every later value of that type takes the same
one without asking again (a dataclass's field names are read once). The
output depends on the value alone, never on whether a plan was cached.
"""

from __future__ import annotations

import dataclasses
import json
import time
import typing
from enum import IntEnum
from typing import Any, Callable

# nanoseconds inside top-level encode()/dumps() calls, summed over every
# Codec (the plans recurse without coming back through either, so a
# nested value is never counted twice); libs/metrics bridges it into
# codec_encode_seconds_total.
# lockfree: one `+=` of a local on a list slot, no call between its read and its write, so no hand-over of the interpreter lock falls inside it
_ENCODE_NS = [0]


def encode_ns() -> int:
    """Nanoseconds spent in top-level ``Codec.encode``/``Codec.dumps``
    calls of this process so far."""
    return _ENCODE_NS[0]


class Codec:
    def __init__(self) -> None:
        self._types: dict[str, type] = {}
        self._hints: dict[type, dict[str, Any]] = {}
        # cls -> (tag, enc, dec); tag -> (cls, enc, dec)
        self._adapters_by_cls: dict[type, tuple[str, Callable, Callable]] = {}
        self._adapters_by_tag: dict[str, tuple[type, Callable, Callable]] = {}
        # type -> the function that encodes its values, built by _plan on
        # the type's first encode and emptied by register*; a type that
        # cannot be encoded gets no entry, so it raises on every call.
        # lockfree: shared by the threads that encode; a plan two of them build at once is the same plan, stored twice
        self._plans: dict[type, Callable[[Any], Any]] = {}
        plans, plan_of = self._plans, self._plan

        def enc(v: Any) -> Any:
            return (plans.get(type(v)) or plan_of(type(v)))(v)

        self._enc = enc

    def register(self, *classes: type) -> None:
        for cls in classes:
            if not dataclasses.is_dataclass(cls):
                raise TypeError(f"{cls.__name__} is not a dataclass")
            self._types[cls.__name__] = cls
        self._plans.clear()

    def register_adapter(
        self,
        cls: type,
        tag: str,
        enc: Callable[[Any], Any],
        dec: Callable[[Any], Any],
    ) -> None:
        """enc(obj) -> jsonable payload; dec(payload) -> obj."""
        self._adapters_by_cls[cls] = (tag, enc, dec)
        self._adapters_by_tag[tag] = (cls, enc, dec)
        self._plans.clear()

    # -- encode ------------------------------------------------------------

    def encode(self, v: Any) -> Any:
        t0 = time.perf_counter_ns()
        try:
            return self._enc(v)
        finally:
            dt = time.perf_counter_ns() - t0
            _ENCODE_NS[0] += dt

    def _plan(self, t: type) -> Callable[[Any], Any]:
        """The rule a value of type ``t`` takes, tried in this order:
        adapter by exact class, registered dataclass, bytes, IntEnum,
        None/bool/int/float/str, list/tuple, dict. Raises TypeError, and
        stores nothing, for a type no rule takes."""
        enc = self._enc
        adapter = self._adapters_by_cls.get(t)
        if adapter is not None:
            tag, to_payload, _ = adapter

            def plan(v: Any) -> Any:
                return {"__a": tag, "v": enc(to_payload(v))}

        elif dataclasses.is_dataclass(t):
            name = t.__name__
            if name not in self._types:
                raise TypeError(f"unregistered dataclass {name}")
            plan = _dataclass_plan(name, t, enc)
        elif issubclass(t, bytes):
            plan = _hexed
        elif issubclass(t, IntEnum):
            plan = int
        elif t is type(None) or issubclass(t, (int, float, str)):
            plan = _itself
        elif issubclass(t, (list, tuple)):

            def plan(v: Any) -> Any:
                return list(map(enc, v))

        elif issubclass(t, dict):

            def plan(v: Any) -> Any:
                return {"__d": [[enc(k), enc(x)] for k, x in v.items()]}

        else:
            raise TypeError(f"cannot encode {t.__name__}")
        self._plans[t] = plan
        return plan

    # -- decode ------------------------------------------------------------

    def _field_hints(self, cls: type) -> dict[str, Any]:
        if cls not in self._hints:
            try:
                self._hints[cls] = typing.get_type_hints(cls)
            except Exception:
                self._hints[cls] = {}
        return self._hints[cls]

    def decode(self, v: Any, hint: Any = None) -> Any:
        if isinstance(v, dict):
            if "__a" in v:
                _, _, dec = self._adapters_by_tag[v["__a"]]
                return dec(self.decode(v["v"]))
            if "__b" in v:
                return bytes.fromhex(v["__b"])
            if "__d" in v:
                return {
                    self.decode(k): self.decode(x) for k, x in v["__d"]
                }
            if "__t" in v:
                cls = self._types[v["__t"]]
                hints = self._field_hints(cls)
                kwargs = {
                    k: self.decode(x, hints.get(k))
                    for k, x in v.items()
                    if k != "__t"
                }
                return cls(**kwargs)
            raise ValueError(f"unknown tagged object: {list(v)}")
        if isinstance(v, list):
            out = [self.decode(x) for x in v]
            if typing.get_origin(hint) is tuple:
                return tuple(out)
            return out
        if (
            isinstance(v, int)
            and not isinstance(v, bool)
            and isinstance(hint, type)
            and issubclass(hint, IntEnum)
        ):
            return hint(v)
        return v

    # -- bytes round-trip --------------------------------------------------

    def dumps(self, obj: Any) -> bytes:
        t0 = time.perf_counter_ns()
        try:
            return json.dumps(self._enc(obj), separators=(",", ":")).encode()
        finally:
            dt = time.perf_counter_ns() - t0
            _ENCODE_NS[0] += dt

    def loads(self, data: bytes) -> Any:
        return self.decode(json.loads(data))


def _itself(v: Any) -> Any:
    return v


def _hexed(v: bytes) -> dict:
    return {"__b": v.hex()}


def _dataclass_plan(name: str, cls: type, enc: Callable) -> Callable:
    """One function that builds a dataclass's tagged dict: ``"__t"``
    first, then each field not starting with ``_`` (in-memory caches such
    as Commit._hash: serializing them would break canonical byte
    equality), in dataclasses.fields order, each read and encoded before
    the next is read."""
    names = [
        f.name for f in dataclasses.fields(cls) if not f.name.startswith("_")
    ]
    items = "".join(f"{n!r}: enc(v.{n}), " for n in names)
    scope = {"name": name, "enc": enc}
    exec(f"def plan(v):\n    return {{'__t': name, {items}}}\n", scope)
    return scope["plan"]
