"""snapshot_to_host_s.resume: host-clock seconds of the snapshot's copy of
every leaf from the device to the host (the program's ``ckpt.save.to_host``
span), per ``ckpt.save``, over the revocations the program's recorder saw:
in a ``--trace 1`` run, the window's first revocation alone."""

from bench.program_spans import mean_per


def read(run):
    return mean_per(run, {"ckpt.save.to_host"}, "ckpt.save")
