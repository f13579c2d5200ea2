"""snapshot_write_s.resume: host-clock seconds of the snapshot's writes
(the program's ``ckpt.save.serialize`` and ``ckpt.save.put`` spans of every
leaf and its ``ckpt.save.manifest``), per ``ckpt.save``, over the
revocations the program's recorder saw: in a ``--trace 1`` run, the
window's first revocation alone."""

from bench.program_spans import mean_per


def read(run):
    return mean_per(run, {"ckpt.save.serialize", "ckpt.save.put", "ckpt.save.manifest"},
                    "ckpt.save")
