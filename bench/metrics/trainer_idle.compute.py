"""Share of the traced window in which the device idled while the trainer
was in ``trainer.compute`` (dispatching the step and pulling its
metrics).  Moves ``train_tok_s``."""
from bench import phases


def read(run):
    return phases.trainer_idle(run, "trainer.compute")
