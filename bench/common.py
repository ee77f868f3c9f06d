"""Shared plumbing of the benchmark: where its files are, how a workload's
configuration and traffic are found by name, and seeds.

Everything a cell needs is looked up by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``  — the configuration as it is run;
* ``bench/reference/<reference>.py`` — the plain reference of the
  architecture that the configuration's ``reference`` names;
* ``bench/traffic/<traffic>.json`` — the traffic mix, whose ``kind`` names
  the generator and driver in ``bench/kinds/<kind>.py``;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric;
* ``bench/limits/<workload>.json`` — the limits of the numbers compared.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
from typing import Optional

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def add_program_to_path() -> None:
    """The system under test lives in ``<checkout>/src``."""
    for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


def workload(name: str, bench: Optional[dict] = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_spec(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic_spec(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    """The cell's limits. A cell without a limits file cannot be judged, so
    it is an error, never a pass."""
    path = BENCH / "limits" / f"{workload_name}.json"
    if not path.exists():
        raise FileNotFoundError(
            f"no limits for {workload_name!r}: bench/limits/"
            f"{workload_name}.json must give every compared number a limit")
    return load_json(path)


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py", f"bench_kind_{kind}")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def reference_module(name: str):
    """The plain reference of an architecture, ``bench/reference/<name>.py``
    (its interface is in ``bench/reference/__init__.py``). A name with no
    file is an error, never a fallback to another reference."""
    path = BENCH / "reference" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no reference {name!r}: the configuration names {path}, which "
            f"does not exist")
    return importlib.import_module(f"bench.reference.{name}")


def model_config(spec: dict, *, rehearse: bool = False):
    """The program's ModelConfig for a configuration file: the registry
    entry it starts from with the file's ``program`` fields replaced. A
    rehearsal keeps the family and shrinks every size to the program's
    CPU smoke variant."""
    add_program_to_path()
    from repro.configs import get_config, reduced_config
    cfg = dataclasses.replace(get_config(spec["registry_id"]),
                              **spec["program"])
    return reduced_config(cfg) if rehearse else cfg


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed above 32 bits (``PRNGKey``
    alone would drop them)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def seed_rng(seed: int, stream: str):
    """A numpy generator for one named stream of a seed's inputs."""
    import numpy as np
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, tag])
