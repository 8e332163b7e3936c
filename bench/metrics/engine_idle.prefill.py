"""Share of the time inside ``eng.step()`` spans in which the device idled
while the engine was in ``engine.prefill`` (one chunk of chunked
prefill, its finiteness pull, prefix hashing and the first token), from
the trace.  Moves ``itl_p95_ms``."""
from bench import phases


def read(run):
    return phases.engine_idle(run, "engine.prefill")
