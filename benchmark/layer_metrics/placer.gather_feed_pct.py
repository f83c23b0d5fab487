"""Share of the usage gather under `_PER_EVAL_SOLVE_LOCK` that the
incremental feed takes: 100 x the summed durations of the `feed.lock_wait`
and `feed.drain` records whose parent is a `placer.gather` record, over
the summed durations of the `placer.gather` records. The records are the
spans that ended inside the run's window. A program whose gather opens no
feed spans gives nothing to read.
"""

FEED = ("feed.lock_wait", "feed.drain")
# record layout of nomad_tpu.obs.trace: name, trace, parent, id, t0, t1
NAME, PARENT, ID, T0, T1 = 0, 2, 3, 4, 5


def read(obs):
    records = obs.get("spans", {}).get("records") or ()
    gathers = {r[ID]: r[T1] - r[T0] for r in records
               if r[NAME] == "placer.gather"}
    feed_s = [r[T1] - r[T0] for r in records
              if r[NAME] in FEED and r[PARENT] in gathers]
    total_s = sum(gathers.values())
    if not feed_s or not total_s:
        return None
    return 100.0 * sum(feed_s) / total_s
