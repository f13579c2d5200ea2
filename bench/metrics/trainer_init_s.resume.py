"""trainer_init_s.resume: host-clock seconds to construct the resumed
``Trainer`` (its ``trainer_init`` span), averaged over the window's
revocations."""


def read(run):
    secs = run["spans"].durations("trainer_init", since=run["window"][0])
    return sum(secs) / len(secs) if secs else None
