"""trainer_init_state_s.resume: host-clock seconds of the resumed
``Trainer``'s fresh random state, model and AdamW init (the program's
``trainer.init_state`` span), which the restore then replaces, per
``trainer.init``, over the revocations the program's recorder saw: in a
``--trace 1`` run, the window's first revocation alone."""

from bench.program_spans import mean_per


def read(run):
    return mean_per(run, {"trainer.init_state"}, "trainer.init")
