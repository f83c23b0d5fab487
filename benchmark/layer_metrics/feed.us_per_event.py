"""Microseconds of the incremental feed's drains per event drained: the
summed durations of the `feed.drain` records that ended in the window
over the sum of their `events` arg. A window in which no drain took an
event, or a program without the span, gives nothing to read.
"""

# record layout of nomad_tpu.obs.trace: name, ..., t0, t1, thread, args
NAME, T0, T1, ARGS = 0, 4, 5, 7


def read(obs):
    drains = [r for r in obs.get("spans", {}).get("records") or ()
              if r[NAME] == "feed.drain"]
    events = sum(r[ARGS].get("events", 0) for r in drains)
    if not events:
        return None
    return 1e6 * sum(r[T1] - r[T0] for r in drains) / events
