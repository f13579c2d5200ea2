"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start to the first timed step):
refuse anything but the TPU chips the cell asks for; turn on the program's
persistent compile cache; build the trial runtime from ``--seed`` as the
traffic's loop says (``TrainLoop``: the benchmark's weights and feed, its
checked steps and, for revoked traffic, one revocation; ``ResumeLoop``: a
whole state made from the seed and one revocation), which compiles every
program the window runs.  Window: ``--seconds`` of the loop's units (a
step, or a revocation cycle), with the profiler on for its first
``trace_seconds`` when ``--trace 1``.  After the window: the peak device
memory; the program's state freed; the reference; the comparison.  The
last stdout line is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, ``breakdown`` (traced runs) and ``checks`` (each number
compared with its limit), also written as the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench.harness import Bench  # noqa: E402


class Refused(RuntimeError):
    """This machine cannot run the cell."""


def check_devices(devices, chips: int, peaks: dict) -> dict:
    """The chips' peaks, or ``Refused``: a run measures TPU chips, never the
    CPU, and only chips whose peaks the table knows."""
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise Refused(f"JAX found platform {platform!r}, not a TPU; the benchmark "
                      "measures the chip and does not fall back")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise Refused(f"device_kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def program_config(conf: dict):
    """The program's model configuration, with the sizes the file states."""
    from repro.configs.base import get_config

    prog = conf["program"]
    return dataclasses.replace(get_config(prog["model"]),
                               **{f: conf[k] for f, k in prog["fields"].items()})


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the benchmark's spans, not every call
    opts.enable_hlo_proto = False     # the reduction needs no HLO
    return opts


class TrainLoop:
    """Steady and revoked traffic: the trial trains, ``run_steps(1)`` a step;
    with ``revoke_every_steps`` K a revocation comes every K steps.  Set-up
    drives its checked steps (and one revocation, when revoked); the window's
    unit is a step, or a revocation cycle of K steps; ``correct`` compares
    the checked steps with the plain reference's."""

    def __init__(self, bench: Bench, name: str, seed: int):
        import jax

        from bench.feed import Feed
        from bench.trial import MemoryStore, Trial

        cell = bench.cell(name)
        self.conf, self.seed = bench.config(cell["config"]), seed
        traffic = bench.traffic(name)
        self.ref = bench.config_module(cell["config"], "ref")
        self.flops = bench.config_module(cell["config"], "flops")
        self.every = traffic["revoke_every_steps"]
        self.feed = Feed(self.conf["vocab_size"], self.conf["batch"], self.conf["seq"],
                         seed, traffic["zipf_s"])
        self.trial = Trial(program_config(self.conf), self.conf, self.ref, self.feed, seed,
                           store=MemoryStore() if self.every else None)
        self.prog = self.trial.checked_steps(revoke_after_first=bool(self.every))
        self.state_bytes = sum(x.nbytes for x in jax.tree.leaves(self.trial.trainer.state))
        self.first_loss = len(self.trial.losses)

    def unit(self):
        if self.every:
            self.trial.revoke()
            for _ in range(self.every - 1):
                self.trial.step()
        else:
            self.trial.step()

    def finish(self, t0: float, t1: float):
        """After the window and the memory reading: frees the trial, runs
        the reference, compares."""
        from bench.check import compare
        from bench.reference import train_readings
        from bench.trial import CHECKED_STEPS

        trial, conf = self.trial, self.conf
        window_losses = trial.losses[self.first_loss:]
        steps = len(window_losses)
        revocations = [r for r in trial.resumes if r[0] >= t0]
        mismatch = trial.restore_mismatches() if self.every else None
        trial.close()
        ref_read = train_readings(self.ref, conf, self.seed,
                                  [self.feed.get_batch(i) for i in range(CHECKED_STEPS)])
        numbers, where = compare(self.prog, ref_read, mismatch)
        tokens_per_step = conf["batch"] * conf["seq"]
        values = {
            "trial_tokens_per_s": steps * tokens_per_step / (t1 - t0),
            "resume_s": (sum(b - a for a, b in revocations) / len(revocations)
                         if revocations else None),
        }
        extra = {"tokens_per_step": tokens_per_step,
                 "train_flops_per_token": self.flops.train_flops_per_token(conf)}
        failed = sum(not math.isfinite(x) for x in window_losses)
        return numbers, where, steps, failed, values, extra


class ResumeLoop:
    """Resume traffic: revocations back to back and no train step.  The
    trial holds a whole state made from the seed; a unit is one revocation,
    from its notice to the restored state on the device; set-up makes one.
    ``correct`` reads every restore back against the seed's bits."""

    def __init__(self, bench: Bench, name: str, seed: int):
        import jax

        from bench.trial import MemoryStore, Trial

        conf = bench.config(bench.cell(name)["config"])
        self.trial = Trial(program_config(conf), conf, None, None, seed, store=MemoryStore())
        self.trial.resume()
        self.state_bytes = sum(x.nbytes for x in jax.tree.leaves(self.trial.trainer.state))

    def unit(self):
        self.trial.resume()

    def finish(self, t0: float, t1: float):
        from bench.check import compare_restores

        trial = self.trial
        window = [i for i, r in enumerate(trial.resumes) if r[0] >= t0]
        stalls = [trial.resumes[i][1] - trial.resumes[i][0] for i in window]
        mismatch = trial.resume_mismatches()
        trial.close()
        numbers, where = compare_restores(mismatch)
        failed = sum(int(np.sum(np.asarray(a) != np.asarray(b)) > 0)
                     for a, b in (trial.checksums[i] for i in window))
        values = {"revocation_stall_s": sum(stalls) / len(stalls) if stalls else None}
        return numbers, where, len(window), failed, values, {}


LOOPS = {"train": TrainLoop, "resume": ResumeLoop}


def run_cell(bench: Bench, name: str, seed: int, seconds: float, trace: bool,
             peak: dict, t_start: float = T_START) -> dict:
    import jax

    from bench.check import judge
    from bench.trace_reduce import find_xplane, reduce_trace

    cell = bench.cell(name)
    traffic = bench.traffic(name)
    limits = bench.limits(name)

    # ---------------------------------------------------------------- set-up
    loop = LOOPS[traffic.get("loop", "train")](bench, name, seed)
    spans = loop.trial.spans

    # ---------------------------------------------------------------- window
    trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    with contextlib.ExitStack() as tracing:
        if trace:
            tracing.enter_context(jax.profiler.trace(
                trace_dir.name, profiler_options=_profiler_options()))
            tracing.enter_context(jax.profiler.TraceAnnotation("window"))
        t0 = time.perf_counter()
        while True:
            loop.unit()
            now = time.perf_counter()
            if now - t0 >= traffic["trace_seconds"]:
                tracing.close()
            if now - t0 >= seconds:
                break
        t1 = time.perf_counter()

    # ------------------------------------------------------------ after it
    devices = jax.local_devices()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[: cell["chips"]])
    numbers, where, attempted, failed, values, extra = loop.finish(t0, t1)
    correct = judge(numbers, limits) and failed == 0
    values["setup_s"] = t0 - t_start

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if not trace:
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in bench.end_to_end(name)
                             if values.get(m["name"]) is not None}
        result["device"] = device
    else:
        reduced = reduce_trace(find_xplane(trace_dir.name),
                               span_names={n for n, _, _ in spans.items})
        trace_dir.cleanup()
        run = dict(extra, spans=spans, window=(t0, t1), trace=reduced,
                   peak_flops_per_s=peak["bf16_flops_per_s"], state_bytes=loop.state_bytes)
        result["metrics"] = {}
        for m in bench.per_layer(name):
            value = bench.reader(m["name"])(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"] = dict(device, busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    return result, where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache  # the system under test

    import jax

    try:
        peak = check_devices(jax.devices(), cell["chips"], bench.peaks())
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result, where = run_cell(bench, args.workload, args.seed, args.seconds,
                             bool(args.trace), peak)
    for k, c in result["checks"].items():  # the numbers compared, last on stderr
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r}; worst: {where[k]})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
