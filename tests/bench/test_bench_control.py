"""The control comes out not correct under each configuration's limits.

The control is the plain reference computed at float8 (per-tensor scaled
e4m3 operands, e5m2 cotangents: the precision below the configuration's
bfloat16) and put in the program's place.  On the chip, at the cells' own
sizes, it reads grad_gap 0.0031-0.0344 (qwen, eight seeds; seven of them
above the limit of 0.0035) and 0.0153-0.0354 (mamba2, eight seeds; limit
0.012) (PERF.md §2).  Here it runs on the CPU at a size a test run holds
(d_model 256, two layers, 4,096 rows of vocabulary; qwen 2 x 128 tokens,
mamba2 1 x 512 at its published SSD widths, where float8 does its damage),
where it fails the same limits; the reference at the configuration's own
precision (bfloat16 weights and operands) passes.  The training cells'
limits are their cell files' (``BENCHMARK.json`` holds those cells back
while the program's cross-entropy is at fault, PERF.md).

The resume traffic's control is a snapshot whose float32 leaves (master
weights and moments) are rounded to bfloat16 on their way to the store:
a run of the cell's own loop at a tiny size reads it not correct.
"""

import json

import pytest

import bench_testlib as tl
from bench_testlib import ROOT

from bench.check import compare, judge
from bench.feed import Feed
from bench.harness import Bench, load_module
from bench.reference import train_readings

CONFIGS = ROOT / "bench" / "configs"
SMALL = {
    "qwen1.5-0.5b": {"hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 4,
                     "head_dim": 64, "intermediate_size": 704, "num_hidden_layers": 2,
                     "vocab_size": 4096, "batch": 2, "seq": 128},
    "mamba2-130m": {"d_model": 256, "n_layer": 2, "vocab_size": 4096,
                    "padded_vocab_size": 4096, "batch": 1, "seq": 512},
}
SEED = 2**31 + 3


@pytest.mark.parametrize("cell", ["trial.qwen05b.steady", "trial.mamba2.steady"])
def test_control_fails_and_configured_precision_passes(cell):
    data = json.loads((ROOT / "bench" / "workloads" / f"{cell}.json").read_text())
    config = data["config"]
    conf = json.loads((CONFIGS / f"{config}.json").read_text())
    conf.update(SMALL[config])
    ref = load_module(CONFIGS / f"{config}_ref.py")
    feed = Feed(conf["vocab_size"], conf["batch"], conf["seq"], SEED, 1.0)
    batches = [feed.get_batch(i) for i in range(3)]
    want = train_readings(ref, conf, SEED, batches)
    limits = data["limits"]
    control, _ = compare(train_readings(ref, conf, SEED, batches, mode="fp8"), want)
    assert not judge(control, limits), control
    sound, _ = compare(train_readings(ref, conf, SEED, batches, mode="bf16"), want)
    assert judge(sound, limits), sound


@pytest.mark.parametrize("variant", ["program", "bf16_snapshot"])
def test_bf16_snapshot_control_fails_and_program_passes(variant, tmp_path):
    from bench.control import readings

    bench = Bench(tl.tiny_root(tmp_path))
    numbers, where = readings(bench, "tiny.mamba2.resume", SEED, variant, cycles=2)
    correct = judge(numbers, bench.limits("tiny.mamba2.resume"))
    assert correct == (variant == "program"), where
