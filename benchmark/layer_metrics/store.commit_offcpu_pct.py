"""Share of the commit rounds' wall time the applier's thread spent off
a CPU: 100 x (1 - sum of `cpu_s` / sum of duration) over the
`plan.commit_round` records that carry `cpu_s` (the thread's own CPU
seconds over the span, `time.thread_time()`). Off-CPU time is the wait
for a lock or for the interpreter lock. A program whose spans carry no
`cpu_s` gives nothing to read.
"""

# record layout of nomad_tpu.obs.trace: name, ..., t0, t1, thread, args
NAME, T0, T1, ARGS = 0, 4, 5, 7


def read(obs):
    rounds = [r for r in obs.get("spans", {}).get("records") or ()
              if r[NAME] == "plan.commit_round" and "cpu_s" in r[ARGS]]
    wall_s = sum(r[T1] - r[T0] for r in rounds)
    if not wall_s:
        return None
    cpu_s = sum(r[ARGS]["cpu_s"] for r in rounds)
    return 100.0 * max(0.0, 1.0 - cpu_s / wall_s)
