"""Find a cell's configuration, traffic and metric files by name.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

    configs  -> the configuration's ``file`` entry (under perfbench/configs)
    traffic  -> perfbench/traffic/<traffic>.json
    metrics  -> perfbench/metrics/<metric name>.py, defining read(ctx)

and an architecture by the configuration's own ``model_type``:

    reference -> perfbench/reference/<model_type>.py: its leaf schema,
                 plain float32 forward and operation counts
    program   -> perfbench/programs/<model_type>.py: the program's
                 ModelConfig and parameter tree for it

so a later change adds a cell, a metric or an architecture by adding
files and entries, without editing any file that is already here.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def load_benchmark(path: Path | None = None) -> dict:
    with open(path or REPO / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _named(bench["configs"], name, "config")
    with open(REPO / entry["file"]) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(ctx) -> float | None`` from metrics/<name>.py."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no metric reader {path}")
    return _load(path, "perfbench_metric_" + name).read


@functools.cache
def _architecture(path: Path, name: str):
    return _load(path, name)


def _by_model_type(kind: str, conf: dict):
    if "model_type" not in conf:
        raise KeyError("the configuration states no model_type")
    model_type = conf["model_type"]
    if not re.fullmatch(r"[A-Za-z0-9_-]+", model_type):
        raise ValueError(f"model_type {model_type!r} is not a module name")
    path = HERE / kind / f"{model_type}.py"
    if not path.exists():
        raise FileNotFoundError(
            f"no {kind} module for model_type {model_type!r}: add "
            f"{path.relative_to(REPO)}")
    return _architecture(path, f"perfbench_{kind}_{model_type}")


def reference(conf: dict):
    """The architecture's reference module, reference/<model_type>.py:
    ``layer_kinds``, ``global_leaves``, ``layer_leaves``, ``logits_at``,
    ``token_ops`` and ``paged_attention``."""
    return _by_model_type("reference", conf)


def program(conf: dict):
    """The architecture's program adapter, programs/<model_type>.py:
    ``model_config``, ``global_tree`` and ``layer_tree``."""
    return _by_model_type("programs", conf)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer(bench: dict, cell: str) -> list[dict]:
    """Per-layer metrics of a cell: those that list it, and those without
    a list whose ``moves`` metric the cell reports."""
    moved = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
