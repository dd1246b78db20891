"""Device seconds of the actor's PPO step (program ``ppo_actor_step``, on
the trace's ``XLA Modules`` line) per traced stage-3 iteration; nothing
unless it ran once in each."""
from bench.lib import programs as PG


def read(rec):
    t = rec.get("trace")
    if t is None:
        return None
    return PG.per_iteration(t.modules, "ppo_actor_step",
                            rec.get("trace_iterations", 0))
