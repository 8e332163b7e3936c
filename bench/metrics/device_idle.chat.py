"""Share of the time inside ``eng.step()`` spans in which no operation
ran on the device, from the trace (waits for arrivals excluded).  Moves
``itl_p95_ms``."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    busy, inside = trace.busy_within(run.trace, "engine.step")
    if inside <= 0:
        return None
    return 100.0 * (1.0 - busy / inside)
