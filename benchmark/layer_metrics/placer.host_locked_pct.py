"""Host time under `_PER_EVAL_SOLVE_LOCK` as a share of the traced window.

The five host phases of the placer's critical section run one after the
other under one lock, so their durations add up without overlap; the
sixth child of `placer.locked`, `placer.device_wait`, is the device's
part and is left out. The records are the spans that ended inside the
run's window and the denominator is the profiler's window, which stops
with the clock or after `trace_seconds`: in a cell whose window outlasts
its trace the numerator would cover more than the denominator, so the
metric lists its cells.
"""

HOST_PHASES = ("placer.gather", "placer.pack", "placer.ship", "placer.fetch",
               "placer.register")
# record layout of nomad_tpu.obs.trace: name, trace, parent, id, t0, t1
NAME, T0, T1 = 0, 4, 5


def read(obs):
    window_s = obs.get("profile", {}).get("window_s")
    records = obs.get("spans", {}).get("records") or ()
    host_s = [r[T1] - r[T0] for r in records if r[NAME] in HOST_PHASES]
    if not window_s or not host_s:
        return None
    return 100.0 * sum(host_s) / window_s
