"""step_mfu.train: model FLOPs of the window's train steps over the summed
host-clock time of their ``train_step`` spans, over the chip's peak, in %.

FLOPs come from the configuration's ``<config>_flops.py`` (forward and
backward as the shapes require, no recomputation); the peak from
``bench/peaks.json``.  Each span ends when the step's loss reaches the host,
so the time holds all of the step's device work and more.
"""


def read(run):
    t0, _ = run["window"]
    secs = run["spans"].durations("train_step", since=t0)
    if not secs:
        return None
    flops = len(secs) * run["tokens_per_step"] * run["train_flops_per_token"]
    return 100.0 * flops / sum(secs) / run["peak_flops_per_s"]
