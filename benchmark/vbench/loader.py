"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a particular cell, configuration, traffic mix, metric or
model family: a later PR adds ``configs/<name>.json``, ``traffic/<name>.json``,
``layer_metrics/<metric>.py``, ``families/<name>.py``, ``reference/<name>.py``
and the entries that name them, and edits no file that is there.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str, bench: dict | None = None) -> dict:
    """Everything one run needs: the workload entry, its configuration and
    traffic files, its metric entries, the peaks table."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _json(os.path.join(ROOT, cfg_entry["file"]))
    from . import traffic as traffic_mod

    traffic_path = os.path.join(HERE, "traffic", entry["traffic"] + ".json")
    traffic = traffic_mod.load(traffic_path)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "workload": entry, "config": config, "traffic": traffic,
        "traffic_path": traffic_path,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "peaks": _json(os.path.join(HERE, "peaks.json")),
    }


def models(config: dict) -> list:
    """[{registry_model, family, reference, sizes}], the default first. A
    configuration's top level describes its default model; ``extra_models``
    names nested groups that describe the others. ``sizes`` holds the
    group's scalars and, of its lists and nested groups, those the family
    names in ``STRUCTURED_SIZES`` (a layer pattern, rope parameters)."""
    out = []
    for group in [config] + [config[k] for k in
                             config.get("extra_models", [])]:
        structured = family(group["family"]).STRUCTURED_SIZES
        out.append({
            "registry_model": group["registry_model"],
            "family": group["family"],
            "reference": group.get("reference", config.get("reference")),
            "sizes": {k: v for k, v in group.items()
                      if isinstance(v, (int, float, str, bool))
                      or k in structured},
        })
    return out


def frozen(value):
    """``value`` with every list a tuple and every dict a sorted tuple of
    items, so that a configuration's sizes can key a cache."""
    if isinstance(value, dict):
        return tuple(sorted((k, frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(frozen(v) for v in value)
    return value


@functools.lru_cache(maxsize=None)
def family(name: str):
    """What the harness knows of one model family (``families/<name>.py``):
    its weights, its sizes against the program, its template, its sample
    and window, its operations, what of a result is compared and how.
    ``families/vit.py`` lists the answers a family gives."""
    return _module(os.path.join(HERE, "families", name + ".py"),
                   "vbench_family_" + "".join(
                       c if c.isalnum() else "_" for c in name))


def reference(name: str):
    return _module(os.path.join(HERE, "reference", name + ".py"),
                   f"vbench_reference_{name}")


def layer_metric(name: str):
    """The reader of one per-layer metric: ``read(ctx) -> float | None``."""
    return _module(os.path.join(HERE, "layer_metrics", name + ".py"),
                   f"vbench_metric_{name.replace('.', '_')}")


def peak(peaks: dict, device_kind: str) -> dict:
    try:
        return peaks["by_device_kind"][device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(peaks['by_device_kind'])}); a share of a peak "
            "against a guessed peak is not a measurement") from None
