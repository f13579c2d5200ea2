"""device_idle_share.resume: in revoked and resume traffic, the share of the
traced window (the first revocation cycle) in which no operation ran on the device,
1 - busy / window, averaged over the chips, in %: the host's part of the
stall."""


def read(run):
    t = run["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
