"""Share of the traced window in which no operation ran on the device.
Moves ``train_tok_s``."""
from bench import trace


def read(run):
    if run.trace is None or trace.window_s(run.trace) <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / trace.window_s(run.trace))
