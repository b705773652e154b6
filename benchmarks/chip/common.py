"""Discovery of the benchmark's data files by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found from the names in
``BENCHMARK.json`` at the root of the checkout:

- ``configs/<config>.json``: model sizes (Hugging Face keys at the top
  level), the engine sizes, the SLO class, the knee rate and the limit of
  the correctness comparison;
- ``traffic/<mix>.json``: parameters of one traffic mix, read by the one
  generator in ``traffic.py``;
- ``metrics/<metric>.py``: a reader ``read(ctx)`` that returns the metric
  or ``None`` when it finds nothing to read;
- ``families/<family>.py``: what belongs to one family of blocks, named by
  a configuration file's ``family`` key (``dense`` where it has none): the
  program's ``ModelConfig``, the weight rules, the reference's layer and
  the weights a token multiplies by (``families/dense.py`` lists them);
- ``peaks.json``: peak rates keyed by ``device_kind``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAMILIES = HERE / "families"      # where ``family`` looks


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(name: str) -> dict:
    """The ``workloads`` entry of ``BENCHMARK.json`` named ``name``."""
    for cell in benchmark_spec()["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def load_mix(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def metric_reader(name: str) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def _load_family(path: Path) -> ModuleType:
    """One module object per file, so that the functions it gives (the
    reference's jitted layer among them) are the same on every call."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_family_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict) -> ModuleType:
    """The module ``families/<family>.py`` of a configuration, by its
    ``family`` key (``dense`` where it has none); an unknown family is an
    error that names the missing file."""
    name = cfg.get("family", "dense")
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"configuration {cfg.get('name')!r} is of family "
                       f"{name!r}, and there is no {path}")
    return _load_family(path)


def metrics_of(cell_name: str, kind: str) -> Dict[str, dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name``
    reports: those with no ``workloads`` key, and those that list it."""
    out = {}
    for m in benchmark_spec()[kind]:
        if "workloads" not in m or cell_name in m["workloads"]:
            out[m["name"]] = m
    return out


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``;
    an unknown kind is an error, never a default."""
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
