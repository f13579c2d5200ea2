"""restore_read_s.resume: host-clock seconds of the restore's reads (the
program's ``ckpt.restore.manifest``, and the ``ckpt.restore.get`` and
``ckpt.restore.decode`` spans of every leaf), per ``ckpt.restore``, over
the revocations the program's recorder saw: in a ``--trace 1`` run, the
window's first revocation alone."""

from bench.program_spans import mean_per


def read(run):
    return mean_per(run, {"ckpt.restore.manifest", "ckpt.restore.get", "ckpt.restore.decode"},
                    "ckpt.restore")
