"""step_device_ms.train: device time of one run of the jitted train-step
program (``jit_train_step`` on the trace's ``XLA Modules`` line), averaged
over the runs inside the traced window, in ms."""

PROGRAM = "jit_train_step"


def read(run):
    m = (run["trace"] or {}).get("modules", {}).get(PROGRAM)
    if not m or not m["count"]:
        return None
    return 1e3 * m["seconds"] / m["count"]
