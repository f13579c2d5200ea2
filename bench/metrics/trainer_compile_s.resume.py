"""trainer_compile_s.resume: seconds JAX reported tracing, lowering, and
compiling or loading from the compilation cache, inside the resumed
``Trainer``'s construction (under the program's ``trainer.init`` span), per
``trainer.init``, over the revocations the program's recorder saw: in a
``--trace 1`` run, the window's first revocation alone."""

from bench.program_spans import compile_seconds_per


def read(run):
    return compile_seconds_per(run, "trainer.init")
