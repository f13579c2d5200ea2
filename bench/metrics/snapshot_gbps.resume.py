"""snapshot_gbps.resume: the trial state's bytes over the host-clock
seconds of ``Trainer.save(blocking=True)`` (its ``snapshot`` span),
averaged over the window's revocations, in GB/s."""


def read(run):
    secs = run["spans"].durations("snapshot", since=run["window"][0])
    return run["state_bytes"] / (sum(secs) / len(secs)) / 1e9 if secs else None
