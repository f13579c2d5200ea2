"""Benchmark driver: one module per paper table/figure + the roofline report.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` trims the
simulation workload count (CI); default runs the full suite.

Perf-trajectory tooling (docs/perf.md):

  --json [PATH]   also write a machine-readable record (default
                  BENCH_simcore.json) with every row and per-suite wall times
  --exact         force the legacy tick-for-tick engine everywhere
                  (REPRO_EXACT_TICKS=1) — the fast path's baseline
  --speedup       run each simulation-bound suite (fig7/fig8/fig9/asha) twice,
                  fast then exact-tick, and record the wall-clock speedup plus
                  a derived-value equivalence cross-check
  --sweep         benchmark the batched multi-replica sweep runtime
                  (repro.sweep) against the naive sequential loop on
                  fig9-style grids; records replicas/sec + speedups
  --append-history
                  append one ``{pr, suite, replicas_per_s, total_speedup}``
                  record per sweep grid to the JSON record's ``trajectory``
                  list (requires --sweep and --json) — the cross-PR perf
                  trail CI's regression smoke reads
  --pr N          PR number stamped on trajectory records (default: the
                  CHANGES.md entry count, one line per landed PR)

JSON row schema: every per-suite row is ``{"name", "value", "unit"}`` —
``value`` is a typed number, never a stringified float.  Timing rows carry
microseconds per call (unit ``"us_per_call"``); derived-metric rows carry
the metric itself with the unit inferred from the row-name suffix
(``_cost_usd`` → ``"usd"``, ``_jct_s``/``_wall_s`` → ``"s"``, ``_pcr`` →
``"ratio"``, ...); rows whose derived value is non-numeric keep it under
``"note"`` with ``value: null``.  ``read_rows`` is the reader shim: it
also yields rows from pre-PR-8 records (``[name, us, "derived"]``
triples) — kept for one release, then triples stop being read.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

# suites that spend their time inside ExecutionEngine.run_until_idle — the
# ones the event-driven fast path (and --speedup) is about
SIM_BOUND = ("fig7", "fig8", "fig9", "asha")


def _derived_map(rows):
    return {name: derived for name, _, derived in rows}


# row-name suffix -> unit for derived-metric rows (docstring schema)
_UNIT_BY_SUFFIX = (
    ("_cost_usd", "usd"), ("_usd", "usd"),
    ("_jct_s", "s"), ("_wall_s", "s"), ("_wall", "us"), ("_s", "s"),
    ("_pcr", "ratio"), ("_ratio", "ratio"), ("_err_mean", "ratio"),
    ("_pct", "percent"),
    ("_per_sec", "1/s"),
    ("_speedup", "x"), ("_speedup_vs_exact", "x"),
    ("_gbps", "GB/s"), ("_gflops", "GFLOP/s"),
)


def _typed_row(name, us, derived) -> dict:
    """One ``{name, value, unit}`` record (see module docstring)."""
    if us:
        row = {"name": name, "value": round(float(us), 3),
               "unit": "us_per_call"}
        if derived not in (None, ""):
            row["note"] = str(derived)
        return row
    try:
        value = float(derived)
    except (TypeError, ValueError):
        return {"name": name, "value": None, "unit": "text",
                "note": str(derived)}
    unit = "scalar"
    for suffix, u in _UNIT_BY_SUFFIX:
        if name.endswith(suffix):
            unit = u
            break
    return {"name": name, "value": value, "unit": unit}


def read_rows(record):
    """Yield ``(name, value, unit)`` from a BENCH record's flat ``rows``.

    Reader shim: pre-PR-8 records stored ``[name, us, "derived"]`` triples
    (stringified numbers, dead 0.0 middle field); those are converted on
    the fly through ``_typed_row`` so consumers only ever see the typed
    schema.  The triple branch is kept for one release."""
    for row in record.get("rows", []):
        if isinstance(row, dict):
            yield row["name"], row["value"], row["unit"]
        else:                               # legacy triple
            name, us, derived = row
            t = _typed_row(name, us, derived)
            yield t["name"], t["value"], t["unit"]


def run_sweep_bench(quick: bool) -> dict:
    """SoA sweep vs generator batching vs the naive loop (θ=0.7, oracle).

    Modes, fastest to slowest — all bit-identical in outcomes
    (tests/test_sweep.py, tests/test_simcore_equiv.py):

    * ``soa`` — the structure-of-arrays stepper (``repro.sweep.soa``), the
      ``SweepRunner`` default; ``replicas_per_sec`` is measured on this mode.
    * ``batched`` — one ``run_cooperative`` generator per replica advanced
      round-robin with cross-replica request batching (the pre-SoA runner).
    * ``naive_warm`` / ``naive_cold`` — one Tuner at a time, with shared
      process-global memos kept warm / dropped per replica.  Skipped on
      grids past 100 replicas, where a naive rep would dominate the suite's
      wall clock without adding information.
    """
    from repro.core.trial import WORKLOADS
    from repro.sweep import SweepRunner, clear_shared_caches, scenario_grid

    names = [w.name for w in WORKLOADS]
    if quick:
        grids = {"fig9_sweep4": scenario_grid(names[:2], range(100, 102),
                                              revpred="oracle", theta=0.7)}
    else:
        grids = {
            # 20 replicas: 5 market seeds x 4 workloads of the fig9 suite
            "fig9_sweep20": scenario_grid(names[:4], range(100, 105),
                                          revpred="oracle", theta=0.7),
            # the full fig9 suite at 20 seeds (the EXPERIMENTS.md grid)
            "fig9_suite_20seed": scenario_grid(names, range(100, 120),
                                               revpred="oracle", theta=0.7),
            # 1000 replicas: 4 workloads x 25 market seeds x 10 engine
            # seeds — the SoA stepper's headline grid (docs/perf.md)
            "fig9_sweep1000": scenario_grid(names[:4], range(100, 125),
                                            revpred="oracle", theta=0.7,
                                            engine_seed=range(10)),
        }
    runner = SweepRunner()
    out = {}
    for gname, specs in grids.items():
        big = len(specs) > 100
        # warm the jit compile + trace synthesis caches (shared by every
        # mode) off the clock
        runner.run(specs)
        modes = ["soa", "batched"] + ([] if big else ["warm", "cold"])
        walls = {m: math.inf for m in modes}
        # interleaved repetitions, best-of each mode: host-load drift on a
        # noisy machine hits every mode instead of whichever ran last.  On
        # big grids the slow baseline runs once (its long wall self-averages
        # the noise) while SoA — the short, claimed measurement — still gets
        # best-of-N.
        reps = 1 if quick else (3 if big else 2)
        for rep in range(reps):
            clear_shared_caches()
            walls["soa"] = min(walls["soa"], runner.run(specs).wall_s)
            if not big or rep == 0:
                clear_shared_caches()
                walls["batched"] = min(
                    walls["batched"],
                    runner.run(specs, mode="batched").wall_s)
            if not big:
                clear_shared_caches()
                walls["warm"] = min(walls["warm"],
                                    runner.run_sequential(specs).wall_s)
                walls["cold"] = min(
                    walls["cold"],
                    runner.run_sequential(specs, cold=True).wall_s)
        rec = {
            "replicas": len(specs),
            "soa_wall_s": round(walls["soa"], 3),
            "batched_wall_s": round(walls["batched"], 3),
            "replicas_per_sec": round(len(specs) / walls["soa"], 2),
            "batched_replicas_per_sec": round(
                len(specs) / walls["batched"], 2),
            "speedup_vs_batched": round(
                walls["batched"] / max(walls["soa"], 1e-9), 2),
        }
        if "warm" in walls:
            rec.update({
                "naive_warm_wall_s": round(walls["warm"], 3),
                "naive_cold_wall_s": round(walls["cold"], 3),
                "speedup_vs_naive_warm": round(
                    walls["warm"] / max(walls["soa"], 1e-9), 2),
                "speedup_vs_naive_cold": round(
                    walls["cold"] / max(walls["soa"], 1e-9), 2),
            })
        out[gname] = rec
        print(f"{gname}_replicas_per_sec,{rec['replicas_per_sec']:.1f},"
              f"vs_batched={rec['speedup_vs_batched']}x"
              f"|vs_warm={rec.get('speedup_vs_naive_warm', 'skip')}x"
              f"|vs_cold={rec.get('speedup_vs_naive_cold', 'skip')}x",
              flush=True)
    return out


def _merge_record(prev, new: dict) -> dict:
    """Fold this invocation's record into an existing BENCH json.

    ``suites`` and ``sweep`` merge per key, so a partial run (``--only
    fig9`` or ``--sweep`` alone) refreshes only the suites it actually ran
    instead of clobbering the whole file.  Top-level scalars (quick,
    exact_ticks, speedup_total) describe the *latest* invocation; the flat
    ``rows`` list is rebuilt from the merged per-suite rows by the caller;
    the ``trajectory`` list always survives (append-only cross-PR trail).
    A record from a different bench (or a pre-merge-format file with no
    per-suite rows) is replaced wholesale.  Legacy per-suite row triples
    from an old file are upgraded to the typed schema on merge so a
    partial refresh never leaves a mixed-format record."""
    if not (isinstance(prev, dict) and prev.get("bench") == new.get("bench")):
        return new
    prev_suites = prev.get("suites", {})
    if prev_suites and not any("rows" in s for s in prev_suites.values()):
        return new      # pre-merge-format record: rows not attributable
    for s in prev_suites.values():
        s["rows"] = [r if isinstance(r, dict) else _typed_row(*r)
                     for r in s.get("rows", [])]
    out = {k: v for k, v in prev.items() if k != "rows"}
    out.update({k: v for k, v in new.items() if k not in ("suites", "sweep")})
    out["suites"] = {**prev_suites, **new.get("suites", {})}
    sweep = {**(prev.get("sweep") or {}), **(new.get("sweep") or {})}
    if sweep:
        out["sweep"] = sweep
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig6,fig7,fig8,fig9,fig10,fig11,fig12,"
                         "asha,roofline,train,ledger,service")
    ap.add_argument("--json", nargs="?", const="BENCH_simcore.json",
                    default=None, metavar="PATH",
                    help="write a JSON benchmark record (default "
                         "BENCH_simcore.json)")
    ap.add_argument("--exact", action="store_true",
                    help="force EngineConfig(exact_ticks=True) process-wide")
    ap.add_argument("--speedup", action="store_true",
                    help="measure fast vs exact-tick wall time per sim-bound "
                         "suite")
    ap.add_argument("--sweep", action="store_true",
                    help="benchmark the batched sweep runtime vs the naive "
                         "replica loop (records replicas/sec)")
    ap.add_argument("--append-history", action="store_true",
                    help="append {pr, suite, replicas_per_s, total_speedup} "
                         "trajectory records for this run's sweep grids to "
                         "the --json record")
    ap.add_argument("--pr", type=int, default=None,
                    help="PR number for --append-history records (default: "
                         "the CHANGES.md entry count)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.exact:
        os.environ["REPRO_EXACT_TICKS"] = "1"
    elif os.environ.pop("REPRO_EXACT_TICKS", None):
        # a leftover exported toggle would silently corrupt the fast-path
        # measurements (and the record would still claim exact_ticks: false)
        print("# ignoring inherited REPRO_EXACT_TICKS (pass --exact instead)",
              file=sys.stderr)

    from benchmarks import (asha_compare, fig6_profiling, fig7_cost_perf,
                            fig8_theta, fig9_refund, fig10_revpred,
                            fig11_earlycurve, fig12_checkpoint, ledger,
                            roofline_report, serve_load, training_trials)
    from repro.core.trial import WORKLOADS

    quick_w = WORKLOADS[:2]
    suite = {
        "fig6": lambda: fig6_profiling.run(),
        "fig7": lambda: fig7_cost_perf.run(
            workloads=quick_w if args.quick else None),
        "fig8": lambda: fig8_theta.run(
            thetas=(0.3, 0.7, 1.0) if args.quick else (0.1, 0.3, 0.5, 0.7, 0.9, 1.0),
            workloads=quick_w if args.quick else None),
        "fig9": lambda: fig9_refund.run(workloads=quick_w if args.quick else None),
        "fig10": lambda: fig10_revpred.run(
            epochs=2 if args.quick else 4, stride=8 if args.quick else 5,
            integrated=not args.quick),
        "fig11": lambda: fig11_earlycurve.run(real=not args.quick),
        "fig12": lambda: fig12_checkpoint.run(
            workloads=quick_w if args.quick else None),
        "asha": lambda: asha_compare.run(
            workloads=quick_w[:1] if args.quick else None),
        "roofline": lambda: roofline_report.run(),
        "ledger": lambda: ledger.run(quick=args.quick),
        "train": lambda: training_trials.run(quick=args.quick),
        "service": lambda: serve_load.run(quick=args.quick),
    }
    only = set(args.only.split(",")) if args.only else set(suite)

    record = {"bench": "simcore", "quick": args.quick,
              "exact_ticks": args.exact, "suites": {}}
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suite.items():
        if name not in only:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:
            failures += 1
            print(f"{name}_ERROR,0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            continue
        wall = time.perf_counter() - t0
        for rname, us, derived in rows:
            print(f"{rname},{us:.1f},{derived}", flush=True)
        print(f"{name}_wall,{wall * 1e6:.1f},ok", flush=True)
        record["suites"][name] = {
            "wall_s": round(wall, 3), "quick": args.quick,
            "rows": [_typed_row(rname, us, derived)
                     for rname, us, derived in rows]}

        if args.speedup and name in SIM_BOUND and not args.exact:
            # the first (printed) run above doubles as warm-up: trace
            # synthesis memos and jit compile caches are shared by both
            # paths.  Time warm runs in interleaved fast/exact pairs and
            # keep the best of each, so host-load drift hits both sides
            fast_wall = exact_wall = math.inf
            try:
                for _ in range(2):
                    t0 = time.perf_counter()
                    fn()
                    fast_wall = min(fast_wall, time.perf_counter() - t0)
                    os.environ["REPRO_EXACT_TICKS"] = "1"
                    try:
                        t0 = time.perf_counter()
                        exact_rows = fn()
                        exact_wall = min(exact_wall,
                                         time.perf_counter() - t0)
                    finally:
                        os.environ.pop("REPRO_EXACT_TICKS", None)
            except Exception as e:
                # a failed re-run shouldn't abort the suite loop or lose
                # the JSON record — match the first-run error handling
                failures += 1
                print(f"{name}_speedup_ERROR,0,{type(e).__name__}:{e}",
                      flush=True)
                traceback.print_exc(file=sys.stderr)
                continue
            exact_derived = _derived_map(exact_rows)
            mismatch = sum(
                1 for k, v in _derived_map(rows).items()
                if str(exact_derived.get(k)) != str(v))
            record["suites"][name].update({
                "fast_wall_s": round(fast_wall, 3),
                "exact_wall_s": round(exact_wall, 3),
                "speedup": round(exact_wall / max(fast_wall, 1e-9), 2),
                "derived_mismatches_vs_exact": mismatch,
            })
            print(f"{name}_speedup_vs_exact,"
                  f"{exact_wall / max(fast_wall, 1e-9):.1f},"
                  f"exact_wall_s={exact_wall:.2f}|mismatches={mismatch}",
                  flush=True)

    if args.sweep and not args.exact:
        try:
            record["sweep"] = run_sweep_bench(args.quick)
        except Exception as e:
            failures += 1
            print(f"sweep_ERROR,0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)

    # the full-mode service load bench records a sweep-style entry too
    # (studies/s, p99 admission latency) so --append-history tracks the
    # service trajectory alongside the SoA grids
    if serve_load.LAST_SWEEP_RECORD:
        record.setdefault("sweep", {})[serve_load.TRAJ_SUITE] = dict(
            serve_load.LAST_SWEEP_RECORD)

    if args.speedup and not args.exact:
        fast = sum(s["fast_wall_s"] for n, s in record["suites"].items()
                   if n in SIM_BOUND and "exact_wall_s" in s)
        exact = sum(s["exact_wall_s"] for n, s in record["suites"].items()
                    if n in SIM_BOUND and "exact_wall_s" in s)
        if fast:
            record["speedup_total"] = round(exact / fast, 2)
            print(f"simcore_speedup_total,{exact / fast:.1f},"
                  f"fast_s={fast:.2f}|exact_s={exact:.2f}", flush=True)

    if args.json:
        # trajectory records only for grids measured by THIS invocation —
        # the merge below folds in older grids that must not re-append
        ran_sweep = dict(record.get("sweep") or {})
        if os.path.exists(args.json):
            try:
                with open(args.json) as fh:
                    record = _merge_record(json.load(fh), record)
            except (OSError, ValueError):
                pass        # unreadable existing file: replace it
        if args.append_history and ran_sweep:
            pr = args.pr
            if pr is None:
                try:
                    with open(os.path.join(os.path.dirname(__file__), "..",
                                           "CHANGES.md")) as fh:
                        pr = sum(1 for ln in fh if ln.strip())
                except OSError:
                    pr = 0
            traj = record.setdefault("trajectory", [])
            for suite, rec in sorted(ran_sweep.items()):
                # total_speedup: SoA vs the coldest baseline this grid ran
                # (naive cold loop where measured, else the generator path)
                traj.append({
                    "pr": pr, "suite": suite,
                    "replicas_per_s": rec["replicas_per_sec"],
                    "total_speedup": rec.get("speedup_vs_naive_cold",
                                             rec.get("speedup_vs_batched")),
                })
        # flat view over the merged per-suite rows, for grep-style consumers
        record["rows"] = [r for s in record["suites"].values()
                          for r in s.get("rows", [])]
        with open(args.json, "w") as fh:
            json.dump(record, fh, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
