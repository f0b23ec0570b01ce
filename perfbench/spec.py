"""What a cell is, read from data: ``BENCHMARK.json`` names the cell, its
configuration file and its traffic file; nothing here knows a cell by name.

No JAX in this module: the parent process of a run imports it.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: Everything a run leaves behind goes under here (``.gitignore`` lists it);
#: the harness creates it.
OUT_DIR = os.path.join(HERE, "out")
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
REHEARSAL_EXIT = 4


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str):
    """A file of the benchmark that is found by name (a metric's reader, a
    configuration's reference), imported from its path."""
    name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def named_module(cfg: dict, key: str):
    """The file a configuration names under ``key`` (its ``reference``, its
    ``layout``, its ``costs``), by its path from the root of the checkout."""
    return load_module(os.path.join(ROOT, cfg[key]))


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def deep_update(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = deep_update(out[key], value)
        else:
            out[key] = value
    return out


def cell(name: str, rehearse: bool = False) -> dict:
    """The cell's entry with its configuration, its traffic and the limits
    of its check loaded (``limits/<cell>.json``).  A rehearsal lays each
    file's own ``rehearsal`` entry (tiny sizes; limits read on the CPU at
    those sizes) over it and changes nothing else."""
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     entry["traffic"] + ".json"))
    limits = load_json(os.path.join(HERE, "limits", name + ".json"))
    limits = limits["rehearsal" if rehearse else "chip"]
    if rehearse:
        config = deep_update(config, config["rehearsal"])
        traffic = deep_update(traffic, traffic["rehearsal"])
    return {"name": name, "chips": entry["chips"], "config": config,
            "config_name": entry["config"],
            "config_file": config_entry["file"], "traffic": traffic,
            "traffic_name": entry["traffic"], "limits": limits,
            "metrics": metrics_of(bench, name)}


def metrics_of(bench: dict, name: str) -> dict:
    """The metric names this cell reports, by group."""
    def mine(group):
        return [m["name"] for m in bench[group]
                if name in m.get("workloads", [name])]
    return {"end_to_end": mine("end_to_end"), "per_layer": mine("per_layer")}
