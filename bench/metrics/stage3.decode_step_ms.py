"""Device milliseconds of one decode step over the traced stage-3
iterations: the device seconds of the ``gen_decode`` program's runs (the
trace's ``XLA Modules`` line) over the decode steps that the program's
tracer counted in the same iterations (counter ``gen.decode_steps``)."""
from bench.lib import programs as PG


def read(rec):
    t, p = rec.get("trace"), rec.get("program")
    if t is None or p is None:
        return None
    return PG.decode_step_ms(t.modules, p["counters"])
