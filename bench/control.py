"""Readings that set a cell's correctness limits, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --variants corrected fp8 half frozen

Not part of a benchmark run.  For each variant and seed it prints one JSON
line with the numbers ``check.py`` compares against the float32 reference:

- ``program``: the trial runtime's checked steps, exactly as a run makes
  them;
- ``corrected``: the same, with only the program's cross-entropy replaced
  by ``corrected_loss`` in this process (PERF.md, Open questions: the
  program's own has a wrong gradient); the lower readings come from these;
- ``fp8``: the control, the reference computed at float8 (the precision
  below the configuration's bfloat16) put in the program's place;
- ``bf16``: the reference computed as the configuration states its
  precision (bfloat16 weights and operands, float32 master weights and
  moments), a sound stand-in for the program;
- ``half``: the reference averaging each step over half of its batch, the
  fault planted in the reference put in the program's place;
- ``frozen``: the reference with its update switched off (learning rate 0),
  a step that returns its state unchanged, put in the program's place.

For a cell of the resume traffic a reading is ``--cycles`` revocations of
the cell's own loop at its own size, and the variants are:

- ``program``: the runtime as a run drives it;
- ``bf16_snapshot``: the control, the snapshot's float32 leaves (master
  weights and moments) rounded to bfloat16 on their way to the store, the
  step below the float32 the configuration keeps them in, which would halve
  the bytes a revocation moves.

Refuses, as ``run.py`` does, a machine without the chip.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.run import Bench, Refused, check_devices, program_config  # noqa: E402

VARIANTS = ("program", "corrected", "fp8", "bf16", "half", "frozen", "bf16_snapshot")


def corrected_loss(self, params, batch, ctx=None):
    """The program's ``Model.loss`` with its max shift held constant on both
    sides of the log-sum-exp.  The program's own adds the max back without
    ``stop_gradient``, which puts an extra +1 on each token's argmax logit in
    the gradient (PERF.md, Open questions)."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers
    from repro.models.context import null_ctx

    cfg, ctx = self.cfg, ctx or null_ctx()
    x, aux = self._backbone(params, batch, ctx)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["unembed"]
    logits = (layers.rms_norm(x, params["ln_f"], cfg.norm_eps) @ w).astype(jnp.float32)
    labels = batch["labels"]
    mx = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - mx), axis=-1)) + mx[..., 0]
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    m = (labels >= 0).astype(jnp.float32)
    xe = jnp.sum((lse - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)
    return xe + aux, {"xent": xe, "aux": aux}


def bf16_snapshot(save_pytree):
    """``save_pytree`` with every float32 leaf rounded to bfloat16 (and kept
    as float32) before it is saved."""
    import jax
    import jax.numpy as jnp

    def save(store, prefix, step, tree, *a, **k):
        tree = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
                            if x.dtype == jnp.float32 else x, tree)
        return save_pytree(store, prefix, step, tree, *a, **k)
    return save


def resume_readings(bench: Bench, name: str, seed: int, variant: str, cycles: int):
    from bench.run import ResumeLoop
    from repro.checkpoint import checkpointer

    own = checkpointer.save_pytree
    if variant == "bf16_snapshot":
        checkpointer.save_pytree = bf16_snapshot(own)
    elif variant != "program":
        raise ValueError(f"variant {variant!r} is not one of the resume traffic's")
    try:
        loop = ResumeLoop(bench, name, seed)
        for _ in range(cycles - 1):
            loop.unit()
        numbers, where, *_ = loop.finish(0.0, 0.0)
    finally:
        checkpointer.save_pytree = own
    return numbers, where


def readings(bench: Bench, name: str, seed: int, variant: str, cycles: int = 3):
    if bench.traffic(name).get("loop") == "resume":
        return resume_readings(bench, name, seed, variant, cycles)
    from bench.check import compare
    from bench.feed import Feed
    from bench.reference import train_readings
    from bench.trial import CHECKED_STEPS, MemoryStore, Trial
    from repro.models.model import Model

    cell = bench.cell(name)
    conf = bench.config(cell["config"])
    traffic = bench.traffic(name)
    ref = bench.config_module(cell["config"], "ref")
    every = traffic["revoke_every_steps"]
    feed = Feed(conf["vocab_size"], conf["batch"], conf["seq"], seed, traffic["zipf_s"])
    batches = [feed.get_batch(i) for i in range(CHECKED_STEPS)]
    mismatch = None
    if variant in ("program", "corrected"):
        own = Model.loss
        if variant == "corrected":
            Model.loss = corrected_loss
        try:
            trial = Trial(program_config(conf), conf, ref, feed, seed,
                          store=MemoryStore() if every else None)
            got = trial.checked_steps(revoke_after_first=bool(every))
            mismatch = trial.restore_mismatches() if every else None
            trial.close()
        finally:
            Model.loss = own
    elif variant in ("fp8", "bf16"):
        got = train_readings(ref, conf, seed, batches, mode=variant)
    elif variant == "half":
        got = train_readings(ref, conf, seed, batches, half_batch=True)
    elif variant == "frozen":
        still = dict(conf, optimizer=dict(conf["optimizer"], lr=0.0))
        got = train_readings(ref, still, seed, batches)
    else:
        raise ValueError(f"unknown variant {variant!r}")
    want = train_readings(ref, conf, seed, batches)
    return compare(got, want, mismatch)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", choices=VARIANTS,
                    default=["corrected", "fp8", "half", "frozen"])
    ap.add_argument("--cycles", type=int, default=3,
                    help="revocations a reading makes, in a resume cell")
    args = ap.parse_args(argv)
    bench = Bench(ROOT)
    import jax

    try:
        check_devices(jax.devices(), bench.cell(args.workload)["chips"], bench.peaks())
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for variant in args.variants:
        for seed in args.seeds:
            numbers, where = readings(bench, args.workload, seed, variant, args.cycles)
            print(json.dumps({"workload": args.workload, "variant": variant, "seed": seed,
                              "numbers": numbers, "where": where}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
