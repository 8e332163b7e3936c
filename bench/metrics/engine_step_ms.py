"""Mean time of one ``eng.step()`` in the window, from the benchmark's own
span around each call (the step ends in the engine's host pulls, so the
span covers its device work).  Moves ``itl_p95_ms``."""


def read(run):
    steps = run.step_spans("engine.step")
    if not steps:
        return None
    return 1e3 * sum(b - a for a, b in steps) / len(steps)
