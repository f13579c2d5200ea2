"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Layout under the benchmark's root (the directory holding BENCHMARK.json):

- ``BENCHMARK.json``'s config entry names the configuration's file; beside
  it lie ``<config>_ref.py`` (its plain reference) and ``<config>_flops.py``
  (its FLOPs a token);
- ``bench/traffic/<traffic>.json``: a traffic mix's parameters, read by the
  one generator (``feed.py``) and trial loop (``run.py``);
- ``bench/workloads/<cell>.json``: the cell's own traffic parameters, laid
  over its mix's, and the limits of its correctness comparison;
- ``bench/metrics/<metric>.py``: one reader a per-layer metric;
- ``bench/peaks.json``: the chips' published peaks, by ``device_kind``.

Adding a configuration, mix, cell or metric is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[1]


def load_module(path: pathlib.Path):
    name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(path.parents[2])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    def __init__(self, root=ROOT):
        self.root = pathlib.Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry named {name!r}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config_file(self, name: str) -> pathlib.Path:
        return self.root / self._entry("configs", name)["file"]

    def config(self, name: str) -> dict:
        return json.loads(self.config_file(name).read_text())

    def config_module(self, name: str, kind: str):
        path = self.config_file(name)
        return load_module(path.with_name(f"{path.stem}_{kind}.py"))

    def cell_file(self, name: str) -> dict:
        data = json.loads((self.dir / "workloads" / f"{name}.json").read_text())
        cell = self.cell(name)
        if (data["config"], data["traffic"]) != (cell["config"], cell["traffic"]):
            raise ValueError(f"bench/workloads/{name}.json names {data['config']}/"
                             f"{data['traffic']}, BENCHMARK.json {cell['config']}/"
                             f"{cell['traffic']}")
        return data

    def traffic(self, name: str) -> dict:
        mix = self.cell(name)["traffic"]
        params = json.loads((self.dir / "traffic" / f"{mix}.json").read_text())
        params.update(self.cell_file(name).get("traffic_params", {}))
        return params

    def limits(self, name: str) -> dict:
        return self.cell_file(name)["limits"]

    def _metrics(self, key: str, cell: str) -> list:
        return [m for m in self.spec[key] if cell in m.get("workloads", [cell])]

    def end_to_end(self, cell: str) -> list:
        return self._metrics("end_to_end", cell)

    def per_layer(self, cell: str) -> list:
        return self._metrics("per_layer", cell)

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py").read

    def peaks(self) -> dict:
        return json.loads((self.dir / "peaks.json").read_text())["devices"]
