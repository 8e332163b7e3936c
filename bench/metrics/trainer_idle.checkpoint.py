"""Share of the traced window in which the device idled while the trainer
was in ``trainer.checkpoint`` (copying the state to the host for a save,
or waiting for a save's write; 0 when none fell in the window).  Moves
``train_tok_s``."""
from bench import phases


def read(run):
    return phases.trainer_idle(run, "trainer.checkpoint")
