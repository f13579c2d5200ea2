"""restore_host_copy_gb.resume: GB the restore copies on the host beyond
the bytes it reads (the program's ``ckpt.host_copy_bytes`` counter, the
decode's ``astype`` copy), per ``ckpt.restore``, over the revocations the
program's recorder saw: in a ``--trace 1`` run, the window's first
revocation alone.  A restore that decodes in place reads 0."""

from bench.program_spans import counts_per


def read(run):
    copied = counts_per(run, ("ckpt.host_copy_bytes",), "ckpt.restore")
    return copied / 1e9 if copied is not None else None
