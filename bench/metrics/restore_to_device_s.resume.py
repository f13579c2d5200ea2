"""restore_to_device_s.resume: host-clock seconds of the restore's hand-off
of every leaf to the device (the program's ``ckpt.restore.to_device``
spans), per ``ckpt.restore``, over the revocations the program's recorder
saw: in a ``--trace 1`` run, the window's first revocation alone.  The wait
for the last transfer falls after it, in the benchmark's ``restore`` span."""

from bench.program_spans import mean_per


def read(run):
    return mean_per(run, {"ckpt.restore.to_device"}, "ckpt.restore")
