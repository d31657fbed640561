"""Name resolution: everything a cell needs is found by the names in
``BENCHMARK.json``, so a later change adds a configuration, a traffic mix,
a metric or a cell as new files and entries and edits none.  A
configuration's architecture is found by its file's ``model_type``
(``bench/blocks/<model_type>.py``)."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
BLOCKS_DIR = BENCH_DIR / "blocks"


class SpecError(Exception):
    """A name in BENCHMARK.json that resolves to nothing."""


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None
    layer: Optional[str] = None
    bound: Optional[float] = None
    workloads: Optional[List[str]] = None


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def traffic_path(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def limits_path(workload: str) -> Path:
    return BENCH_DIR / "limits" / f"{workload}.json"


def reader_path(metric: str) -> Path:
    return BENCH_DIR / "metrics" / f"{metric}.py"


def block_path(model_type: str) -> Path:
    return BLOCKS_DIR / f"{model_type}.py"


def _metric(d: dict) -> Metric:
    return Metric(name=d["name"], unit=d["unit"], better=d["better"],
                  source=d["source"], moves=d.get("moves"),
                  layer=d.get("layer"), bound=d.get("bound"),
                  workloads=d.get("workloads"))


def cell_metrics(bench: dict, workload: str):
    """(end_to_end, per_layer) metrics this cell reports.  An end-to-end
    metric without ``workloads`` is reported everywhere; a per-layer one
    without it wherever the end-to-end metric it moves is reported."""
    e2e = [_metric(m) for m in bench["end_to_end"]
           if m.get("workloads") is None or workload in m["workloads"]]
    names = {m.name for m in e2e}
    per = []
    for d in bench["per_layer"]:
        m = _metric(d)
        if m.workloads is not None:
            if workload in m.workloads:
                per.append(m)
        elif m.moves in names:
            per.append(m)
    return e2e, per


def resolve(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    cfg_path = root / configs[w["config"]]["file"]
    tr_path = traffic_path(w["traffic"])
    lim_path = limits_path(workload)
    for p in (cfg_path, tr_path, lim_path):
        if not p.is_file():
            raise SpecError(f"workload {workload!r}: missing {p}")
    config = load_json(cfg_path)
    if not block_path(config["model_type"]).is_file():
        raise SpecError(f"workload {workload!r}: no block for model_type "
                        f"{config['model_type']!r}: "
                        f"{block_path(config['model_type'])}")
    e2e, per = cell_metrics(bench, workload)
    for m in e2e + per:
        if not reader_path(m.name).is_file():
            raise SpecError(f"metric {m.name!r} has no reader "
                            f"{reader_path(m.name)}")
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=load_json(tr_path),
                limits=load_json(lim_path), end_to_end=e2e, per_layer=per)


def _load_module(prefix: str, path: Path):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_block(model_type: str):
    """The architecture module ``bench/blocks/<model_type>.py``:
    ``program(c, m)`` (the program's ModelConfig of the block at the
    configuration file's sizes, ``m`` the repo configuration it names),
    ``logits(c, params, tokens, lowp, positions)`` (the plain reference's
    forward), and the per-layer parameter counts
    ``stored_layer_params(c)``, ``active_layer_params(c)`` and
    ``extra_params(c)``."""
    path = block_path(model_type)
    if not path.is_file():
        raise SpecError(f"no block for model_type {model_type!r}: {path}")
    return _load_module("bench_block_", path)


def load_reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return _load_module("bench_metric_", reader_path(metric)).read


def load_kind(kind: str):
    """The driver module ``bench/kinds/<kind>.py`` named by a traffic
    file's ``kind``."""
    path = BENCH_DIR / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise SpecError(f"no driver for traffic kind {kind!r}: {path}")
    return _load_module("bench_kind_", path)
