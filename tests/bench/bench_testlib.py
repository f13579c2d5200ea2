"""Shared helpers for the benchmark's CPU tests: the repository root on the
path, and a benchmark root of tiny cells built from the real files."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "qwen1.5-0.5b": {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
                     "head_dim": 16, "intermediate_size": 176, "num_hidden_layers": 2,
                     "vocab_size": 512,
                     "batch": 2, "seq": 32},
    "mamba2-130m": {"d_model": 64, "n_layer": 2, "vocab_size": 250, "padded_vocab_size": 256,
                    "d_state": 16, "headdim": 16, "chunk_size": 16, "batch": 2, "seq": 64},
}
CELLS = {"tiny.qwen.steady": ("qwen1.5-0.5b", "steady", "trial.qwen05b.steady"),
         "tiny.mamba2.steady": ("mamba2-130m", "steady", "trial.mamba2.steady"),
         "tiny.mamba2.revoked": ("mamba2-130m", "revoked", "trial.mamba2.revoked"),
         "tiny.mamba2.resume": ("mamba2-130m", "resume", "trial.mamba2.resume")}
REVOKE_EVERY = 2


def tiny_root(tmp: pathlib.Path, limits=None) -> pathlib.Path:
    """A benchmark root whose cells are the real ones at tiny sizes: the
    real traffic mixes, metric readers, peaks, references and FLOPs files,
    the configurations shrunk; the real cells' limits unless ``limits``.
    The training cells, which ``BENCHMARK.json`` holds back while the
    program's cross-entropy is at fault (PERF.md), come from their own cell
    files, and the end-to-end metrics they would report are added."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    src, dst = ROOT / "bench", tmp / "bench"
    for sub in ("traffic", "metrics"):
        shutil.copytree(src / sub, dst / sub)
    shutil.copy(src / "peaks.json", dst / "peaks.json")
    (dst / "configs").mkdir()
    (dst / "workloads").mkdir()
    configs = []
    for name, sizes in TINY.items():
        conf = json.loads((src / "configs" / f"{name}.json").read_text())
        conf.update(sizes)
        (dst / "configs" / f"tiny-{name}.json").write_text(json.dumps(conf))
        for kind in ("ref", "flops"):
            shutil.copy(src / "configs" / f"{name}_{kind}.py",
                        dst / "configs" / f"tiny-{name}_{kind}.py")
        configs.append({"name": f"tiny-{name}", "source": "tiny copy",
                        "file": f"bench/configs/tiny-{name}.json", "reduced": [],
                        "why": "CPU test"})
    cells = []
    for cell, (config, traffic, real_cell) in CELLS.items():
        data = json.loads((src / "workloads" / f"{real_cell}.json").read_text())
        data["config"] = f"tiny-{config}"
        if limits is not None:
            keep = {"steady": set(limits) - {"restore_mismatch"}, "revoked": set(limits),
                    "resume": {"restore_mismatch"}}[traffic]
            data["limits"] = {k: v for k, v in limits.items() if k in keep}
        if traffic == "revoked":
            data["traffic_params"] = {"revoke_every_steps": REVOKE_EVERY}
        (dst / "workloads" / f"{cell}.json").write_text(json.dumps(data))
        cells.append({"name": cell, "config": f"tiny-{config}", "traffic": traffic,
                      "chips": 1, "why": "CPU test"})
    cell_of = {real_cell: cell for cell, (_, _, real_cell) in CELLS.items()}
    spec = dict(real, configs=configs, workloads=cells)
    for key in ("end_to_end", "per_layer"):
        spec[key] = [dict(m, workloads=[cell_of[w] for w in m["workloads"]])
                     if "workloads" in m else m for m in real[key]]
    spec["end_to_end"] += [dict(m, bound=0.25, source="host_clock", workloads=cells_)
                           for m, cells_ in TRAIN_METRICS]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


TRAIN_METRICS = [
    ({"name": "trial_tokens_per_s", "unit": "tokens/s", "better": "higher"},
     ["tiny.qwen.steady", "tiny.mamba2.steady"]),
    ({"name": "resume_s", "unit": "s", "better": "lower"}, ["tiny.mamba2.revoked"]),
]

V5E = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]


# The program's cross-entropy has a wrong gradient (PERF.md, Open questions);
# tests that need a sound run patch this correct form in, so that what turns
# ``correct`` false is the fault they plant and nothing else.
from bench.control import corrected_loss  # noqa: E402,F401
