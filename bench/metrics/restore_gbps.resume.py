"""restore_gbps.resume: the trial state's bytes over the host-clock
seconds of ``Trainer.restore()`` until the state is on the device (its
``restore`` span), averaged over the window's revocations, in GB/s."""


def read(run):
    secs = run["spans"].durations("restore", since=run["window"][0])
    return run["state_bytes"] / (sum(secs) / len(secs)) / 1e9 if secs else None
