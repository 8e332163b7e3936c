"""Share of the time inside ``eng.step()`` spans in which the device idled
while the engine was in ``engine.decode`` (block-table growth, the
decode dispatch and the (B,) isfinite pull), from the trace.  Moves
``itl_p95_ms``."""
from bench import phases


def read(run):
    return phases.engine_idle(run, "engine.decode")
