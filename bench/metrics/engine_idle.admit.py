"""Share of the time inside ``eng.step()`` spans in which the device idled
while the engine was in ``engine.admit`` (deadline expiry, the block-
table check and admission), from the trace.  Moves ``itl_p95_ms``."""
from bench import phases


def read(run):
    return phases.engine_idle(run, "engine.admit")
