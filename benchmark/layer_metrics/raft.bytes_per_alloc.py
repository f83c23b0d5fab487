"""Bytes appended to the leader's raft log per allocation committed in
the window: the delta of `nomad.raft.append_bytes` over the rows the
store applied from plan results (the `rows` argument of the
`store.apply` spans that ended in the window; under raft the FSM's
thread opens them). A program without the counter, or a window without
a committed row, gives nothing to read.
"""

# record layout of nomad_tpu.obs.trace: name, ..., args
NAME, ARGS = 0, 7


def read(obs):
    nbytes = obs.get("counters", {}).get("registry", {}).get(
        "nomad.raft.append_bytes")
    rows = sum(r[ARGS].get("rows", 0)
               for r in obs.get("spans", {}).get("records") or ()
               if r[NAME] == "store.apply")
    if nbytes is None or not rows:
        return None
    return nbytes / rows
