"""Resolve a cell of ``BENCHMARK.json`` into its files (no JAX here)."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class SpecError(ValueError):
    """A name in BENCHMARK.json that resolves to no file, or a bad file."""


def load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            out = json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None
    if not isinstance(out, dict):
        raise SpecError(f"{path}: expected a JSON object")
    return out


def load_module(path: str, name: str) -> ModuleType:
    """A reader or reference by file path, so a benchmark directory
    other than the installed one (tests build one) resolves its own."""
    if not os.path.exists(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    kind: str  # "end_to_end" | "per_layer"
    reader: str
    args: dict
    read: object  # callable(ctx, **args) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic mix file
    end_to_end: list[Metric]
    per_layer: list[Metric]
    bench_dir: str  # directory holding configs/, traffic/, readers/ ...
    root: str  # directory holding BENCHMARK.json


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str = REPO) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(cell_name: str, root: str = REPO) -> Cell:
    """The cell ``cell_name`` of ``<root>/BENCHMARK.json`` with every
    file it names loaded; raises ``SpecError`` on anything missing."""
    bench = load_benchmark(root)
    bench_dir = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SpecError(
            f"unknown workload {cell_name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"cell {cell_name}: unknown config {w['config']!r}")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))

    def metrics(kind: str, folder: str) -> list[Metric]:
        out = []
        for m in bench[kind]:
            if not _applies(m, cell_name):
                continue
            d = load_json(os.path.join(bench_dir, folder, m["name"] + ".json"))
            mod = load_module(
                os.path.join(bench_dir, "readers", d["reader"] + ".py"),
                f"cellbench_reader_{d['reader']}")
            out.append(Metric(m["name"], m["unit"], kind, d["reader"],
                              d.get("args", {}), mod.read))
        return out

    return Cell(
        name=cell_name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=metrics("end_to_end", "end_to_end"),
        per_layer=metrics("per_layer", "layer_metrics"),
        bench_dir=bench_dir, root=root,
    )


def service_env(config: dict) -> dict[str, str]:
    """The service's environment from a configuration file: ``env`` as
    written, plus ``env_json`` entries rendered to JSON strings with
    ``"$key"`` values replaced by the file's top-level ``key`` — so the
    published sizes are written once and the model's own config
    variable (``LLAMA_CONFIG`` …) is derived from them."""
    env = {k: str(v) for k, v in config.get("env", {}).items()}
    for var, obj in config.get("env_json", {}).items():
        env[var] = json.dumps({k: subst(v, config) for k, v in obj.items()})
    return env


def subst(v, config: dict):
    if isinstance(v, str) and v.startswith("$"):
        if v[1:] not in config:
            raise SpecError(f"env_json refers to {v} which the config lacks")
        return config[v[1:]]
    return v
