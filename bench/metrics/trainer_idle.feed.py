"""Share of the traced window in which the device idled while the trainer
was in ``trainer.feed`` (making the batch and putting it on the device).
Moves ``train_tok_s``."""
from bench import phases


def read(run):
    return phases.trainer_idle(run, "trainer.feed")
