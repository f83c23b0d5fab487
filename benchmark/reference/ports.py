"""Plain reference for the port guarantee: a recount per node.

"Every allocation of a group that asks ports holds exactly the ports it
asked, each inside its node's dynamic range and outside its reserved
ports, and no value twice on a node." Plain sets and loops over plain
data; nothing here imports the program (its `NetworkIndex` least of
all: that is the code under test).

    nodes   {node id: {"min": lowest dynamic port, "max": highest,
                       "reserved": [port numbers the agent keeps]}}
    allocs  [{"id", "node", "ports": [[label, value], ...],
              "dynamic": [labels the group asked a dynamic port for],
              "static": [[label, value] the group asked by number]}]
            the live allocations, each materialised (a block's
            positions one by one, with the ports they hold: none, until
            blocks carry ports)

`violations()` returns one line a violation, nothing where the
guarantee holds; `census()` what was read, so that a pass over nothing
shows.
"""

from __future__ import annotations


def violations(nodes: dict, allocs: list) -> list:
    out = []
    on_node: dict = {}
    for a in allocs:
        node = nodes.get(a["node"])
        if node is None:
            out.append(f"{a['id']}: on a node the cluster does not have "
                       f"({a['node']})")
            continue
        held = {}
        for label, value in a["ports"]:
            if label in held:
                out.append(f"{a['id']}: label {label} holds two ports")
            held[label] = value
        static = dict(a["static"])
        asked = set(a["dynamic"]) | set(static)
        for label in sorted(asked - set(held)):
            out.append(f"{a['id']}: missing the port it asked as {label}")
        for label in sorted(set(held) - asked):
            out.append(f"{a['id']}: surplus port {label}={held[label]}")
        for label, value in held.items():
            if label in static:
                if value != static[label]:
                    out.append(f"{a['id']}: {label} asked {static[label]}, "
                               f"holds {value}")
            elif not node["min"] <= value <= node["max"]:
                out.append(f"{a['id']}: {label}={value} outside the node's "
                           f"dynamic range {node['min']}-{node['max']}")
            if value in node["reserved"]:
                out.append(f"{a['id']}: {label}={value} is reserved on "
                           f"{a['node']}")
            owners = on_node.setdefault(a["node"], {})
            if value in owners:
                out.append(f"{a['node']}: port {value} twice, on "
                           f"{owners[value]} and {a['id']}")
            owners[value] = a["id"]
    return out


def census(nodes: dict, allocs: list) -> dict:
    return {"allocations": len(allocs),
            "ports": sum(len(a["ports"]) for a in allocs),
            "asked": sum(len(a["dynamic"]) + len(a["static"])
                         for a in allocs),
            "nodes": len(nodes),
            "nodes_holding": len({a["node"] for a in allocs if a["ports"]})}
