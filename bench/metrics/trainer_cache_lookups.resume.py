"""trainer_cache_lookups.resume: executables the resumed ``Trainer``'s
construction loaded from or added to the compilation cache (JAX's cache
hits plus misses under the program's ``trainer.init`` span), per
``trainer.init``, over the revocations the program's recorder saw: in a
``--trace 1`` run, the window's first revocation alone.  A trainer that
reuses its compiled functions reads 0."""

from bench.program_spans import cache_lookups_per


def read(run):
    return cache_lookups_per(run, "trainer.init")
