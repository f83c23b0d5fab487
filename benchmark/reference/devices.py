"""Plain reference for the device guarantees: a recount per node.

"Every allocation of a group that asks a device holds exactly the
instances it asked, of groups on its node that match the ask's name and
meet its constraints; no instance id is held twice on a node, and none
that is not one of its node's groups'; no node is over capacity; a
spread job is even over the racks." Plain dicts, sets and loops over
plain data, with a unit table of its own; nothing here imports the
program (its `DeviceIndex`, `allocs_fit` and attribute parser are the
code under test).

    nodes   {node id: {"cpu", "mem": capacity, "rack": value,
                       "groups": [{"id", "vendor", "type", "name",
                                   "instances": [ids],
                                   "attributes": {name: value}}]}}
    allocs  [{"id", "node", "job", "cpu", "mem",
              "devices": {group id: [instance ids]},
              "asks": [{"name", "count",
                        "constraints": [[ltarget, operand, rtarget]]}]}]
            the live allocations, each materialised

An attribute value is a number (int or float, no unit), a bool, or a
string, which may be a number with a unit ("11 GiB", "1480 MHz").
`violations()` returns one line a violation, nothing where the
guarantees hold; `census()` what was read, so that a pass over nothing
shows.
"""

from __future__ import annotations

import re

# unit -> (family, factor to the family's base)
UNITS = {"B": ("byte", 1), "B/s": ("byte/s", 1), "Hz": ("hz", 1),
         "kHz": ("hz", 10 ** 3), "MHz": ("hz", 10 ** 6),
         "GHz": ("hz", 10 ** 9), "mW": ("watt", 0.001), "W": ("watt", 1),
         "kW": ("watt", 10 ** 3), "MW": ("watt", 10 ** 6),
         "GW": ("watt", 10 ** 9), "kB": ("byte", 10 ** 3),
         "kB/s": ("byte/s", 10 ** 3)}
for _i, _p in enumerate("KMGTPE", start=1):
    UNITS[_p + "B"] = ("byte", 1000 ** _i)
    UNITS[_p + "iB"] = ("byte", 1024 ** _i)
    UNITS[_p + "B/s"] = ("byte/s", 1000 ** _i)
    UNITS[_p + "iB/s"] = ("byte/s", 1024 ** _i)

_NUMBER = re.compile(r"([+-]?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?)\s*(\S*)")


def value_of(raw):
    """-> (kind, value, family): a number in its family's base unit
    (family None without a unit), a bool, or a string."""
    if isinstance(raw, bool):
        return "bool", raw, None
    if isinstance(raw, (int, float)):
        return "number", raw, None
    text = str(raw)
    m = _NUMBER.fullmatch(text)
    if m and (m.group(2) == "" or m.group(2) in UNITS):
        number = float(m.group(1))
        if m.group(2) == "":
            return "number", number, None
        family, factor = UNITS[m.group(2)]
        return "number", number * factor, family
    if text in ("true", "false", "True", "False", "TRUE", "FALSE",
                "t", "f", "T", "F"):
        return "bool", text.lower().startswith("t"), None
    return "string", text, None


def compare(left, right):
    """-1, 0, 1, or None where the two do not compare (other kinds, a
    unit on one side only, or units of two families)."""
    lk, lv, lf = value_of(left)
    rk, rv, rf = value_of(right)
    if lk != rk or lf != rf:
        return None
    return (lv > rv) - (lv < rv)


def target(text: str, group: dict):
    """A constraint side against one device group -> (value, found)."""
    if not text.startswith("${"):
        return text, True
    key = text[2:-1]
    if key in ("device.model", "device.name"):
        return group["name"], True
    if key in ("device.vendor", "device.type"):
        return group[key.split(".")[1]], True
    if key.startswith("device.attr."):
        attrs = group["attributes"]
        name = key[len("device.attr."):]
        return attrs.get(name), name in attrs
    return None, False


ORDER = {"=": (0,), "==": (0,), "is": (0,), "<": (-1,), "<=": (-1, 0),
         ">": (1,), ">=": (0, 1)}


def meets(group: dict, ltarget: str, operand: str, rtarget: str) -> bool:
    left, lfound = target(ltarget, group)
    right, rfound = target(rtarget, group)
    if operand == "is_set":
        return lfound
    if operand in ("!=", "not"):
        if not (lfound or rfound):
            return False
        if not (lfound and rfound):
            return True
        c = compare(left, right)
        return c is not None and c != 0
    if operand not in ORDER:
        raise NotImplementedError(f"operator {operand!r}")
    if not (lfound and rfound):
        return False
    c = compare(left, right)
    return c is not None and c in ORDER[operand]


def serves(group: dict, ask: dict) -> bool:
    """The group matches the ask's name ("type", "vendor/type" or
    "vendor/type/name") and meets every one of its constraints."""
    parts = ask["name"].split("/")
    mine = [group["vendor"], group["type"], group["name"]]
    named = (parts == [group["type"]] if len(parts) == 1
             else parts == mine[:len(parts)])
    return named and all(meets(group, *c) for c in ask["constraints"])


def violations(nodes: dict, allocs: list) -> list:
    out = []
    held: dict = {}          # node id -> {(group id, instance): alloc id}
    used: dict = {}          # node id -> [cpu, mem]
    for a in allocs:
        node = nodes.get(a["node"])
        if node is None:
            out.append(f"{a['id']}: on a node the cluster does not have "
                       f"({a['node']})")
            continue
        u = used.setdefault(a["node"], [0.0, 0.0])
        u[0] += a["cpu"]
        u[1] += a["mem"]
        groups = {g["id"]: g for g in node["groups"]}
        owners = held.setdefault(a["node"], {})
        for gid, ids in a["devices"].items():
            for i in ids:
                if gid not in groups or i not in groups[gid]["instances"]:
                    out.append(f"{a['id']}: holds {gid} {i}, which is not "
                               f"a device of {a['node']}")
                if (gid, i) in owners:
                    out.append(f"{a['node']}: {gid} {i} twice, on "
                               f"{owners[(gid, i)]} and {a['id']}")
                owners[(gid, i)] = a["id"]
        counted = set()
        for k, ask in enumerate(a["asks"]):
            mine = [gid for gid in a["devices"]
                    if gid in groups and serves(groups[gid], ask)]
            if not any(serves(g, ask) for g in node["groups"]):
                out.append(f"{a['id']}: on {a['node']}, which has no "
                           f"group serving ask {k} ({ask['name']})")
            n = sum(len(a["devices"][gid]) for gid in mine)
            if n != ask["count"]:
                out.append(f"{a['id']}: holds {n} instance(s) for ask {k} "
                           f"({ask['name']}), asked {ask['count']}")
            counted.update(mine)
        for gid in sorted(set(a["devices"]) - counted):
            out.append(f"{a['id']}: surplus instances of {gid}")
    for node_id, (cpu, mem) in used.items():
        node = nodes[node_id]
        if cpu > node["cpu"] or mem > node["mem"]:
            out.append(f"{node_id}: over capacity, {cpu} / {node['cpu']} "
                       f"MHz, {mem} / {node['mem']} MB")
    return out


def spread_violations(nodes: dict, allocs: list, spread_jobs: set,
                      slack: int) -> list:
    """A spread job's allocations per rack, over every rack that holds
    a node with a group serving each of the job's asks: max - min at
    most `slack`."""
    per_job: dict = {}
    for a in allocs:
        if a["job"] not in spread_jobs or a["node"] not in nodes:
            continue
        if a["job"] not in per_job:
            per_job[a["job"]] = dict.fromkeys(sorted({
                n["rack"] for n in nodes.values()
                if all(any(serves(g, ask) for g in n["groups"])
                       for ask in a["asks"])}), 0)
        counts = per_job[a["job"]]
        rack = nodes[a["node"]]["rack"]
        counts[rack] = counts.get(rack, 0) + 1
    out = []
    for job, counts in sorted(per_job.items()):
        if max(counts.values()) - min(counts.values()) > slack:
            out.append(f"{job}: uneven over the racks, "
                       f"{sorted(counts.values())}")
    return out


def census(nodes: dict, allocs: list) -> dict:
    return {"allocations": len(allocs),
            "device_allocations": sum(1 for a in allocs if a["asks"]),
            "instances": sum(len(ids) for a in allocs
                             for ids in a["devices"].values()),
            "nodes": len(nodes),
            "gpu_nodes": sum(1 for n in nodes.values() if n["groups"])}
