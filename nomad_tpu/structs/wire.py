"""Type-tagged wire codec for replicated commands and persistence.

The API codec (api/codec.py) is schema-directed: each route knows its
payload type, so dicts carry no type tags. The raft log, FSM snapshots,
and the socket transport have no such schema — a command's args can hold
any struct — so this codec tags dataclass values with their class name
and inflates them back through a registry of every struct dataclass.
(The reference gets this for free from Go's msgpack codec over the
registered request structs, nomad/structs/structs.go msgpack handles.)
"""

from __future__ import annotations

import base64
import dataclasses
from typing import Any, Dict

import numpy as np

_REGISTRY: Dict[str, type] = {}


def _build_registry() -> None:
    if _REGISTRY:
        return
    from . import (alloc, constraint, deployment, evaluation, job, network,
                   node, operator, plan, resources, services, variables,
                   volumes)
    from ..acl import auth as acl_auth
    from ..acl import policy as acl_policy
    from ..acl import tokens as acl_tokens

    for mod in (alloc, constraint, deployment, evaluation, job, network,
                node, operator, plan, resources, services, variables,
                volumes, acl_auth, acl_policy, acl_tokens):
        for name in dir(mod):
            obj = getattr(mod, name)
            if isinstance(obj, type) and dataclasses.is_dataclass(obj):
                existing = _REGISTRY.get(obj.__name__)
                if existing is not None and existing is not obj:
                    raise RuntimeError(
                        f"wire codec name collision: {obj.__name__}")
                _REGISTRY[obj.__name__] = obj


def wire_encode(obj: Any) -> Any:
    """Lower any command/struct graph to JSON-safe values, with type tags
    where the shape alone can't recover the Python type."""
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return obj
    if isinstance(obj, bytes):
        return {"__b": base64.b64encode(obj).decode("ascii")}
    if isinstance(obj, np.ndarray):
        return {"__nd": obj.tolist(), "__dt": str(obj.dtype)}
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, tuple):
        return {"__tp": [wire_encode(v) for v in obj]}
    if isinstance(obj, (set, frozenset)):
        return {"__set": [wire_encode(v) for v in sorted(obj, key=repr)]}
    if isinstance(obj, list):
        return [wire_encode(v) for v in obj]
    if isinstance(obj, dict):
        if all(isinstance(k, str) and not k.startswith("__") for k in obj):
            return {k: wire_encode(v) for k, v in obj.items()}
        # tuple/other keys (store index keys) ride as pair lists
        return {"__d": [[wire_encode(k), wire_encode(v)]
                        for k, v in obj.items()]}
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        _build_registry()
        cls = type(obj)
        if cls.__name__ not in _REGISTRY:
            raise TypeError(f"unregistered wire type {cls.__name__}")
        # "_"-prefixed fields are derived caches (e.g. Node._avail_vec);
        # they never ride the wire and decode falls back to the default.
        # So does a field a struct names in `_wire_omit_none` while it
        # holds None: a record without it encodes as before it existed
        omit = getattr(cls, "_wire_omit_none", ())
        fields = {f.name: wire_encode(getattr(obj, f.name))
                  for f in dataclasses.fields(obj)
                  if not f.name.startswith("_")
                  and not (f.name in omit and getattr(obj, f.name) is None)}
        return {"__t": cls.__name__, "__f": fields}
    raise TypeError(f"cannot wire-encode {type(obj).__name__}")


def wire_decode(data: Any) -> Any:
    if data is None or isinstance(data, (bool, int, float, str)):
        return data
    if isinstance(data, list):
        return [wire_decode(v) for v in data]
    if isinstance(data, dict):
        if "__b" in data and len(data) == 1:
            return base64.b64decode(data["__b"])
        if "__nd" in data:
            return np.asarray(data["__nd"], dtype=data.get("__dt", "float64"))
        if "__tp" in data and len(data) == 1:
            return tuple(wire_decode(v) for v in data["__tp"])
        if "__set" in data and len(data) == 1:
            return set(wire_decode(v) for v in data["__set"])
        if "__d" in data and len(data) == 1:
            return {wire_decode(k): wire_decode(v) for k, v in data["__d"]}
        if "__t" in data:
            _build_registry()
            cls = _REGISTRY.get(data["__t"])
            if cls is None:
                raise TypeError(f"unknown wire type {data['__t']}")
            fields = {k: wire_decode(v) for k, v in data["__f"].items()}
            known = {f.name for f in dataclasses.fields(cls)}
            return cls(**{k: v for k, v in fields.items() if k in known})
        return {k: wire_decode(v) for k, v in data.items()}
    raise TypeError(f"cannot wire-decode {type(data).__name__}")
