"""Find a cell's files by name and build what the program is given.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files themselves sit under the benchmark root's ``bench/``:

- ``configs/<config>.json``: the model as it is run, the ``family`` it
  belongs to, its source and its cuts;
- ``families/<family>.py``: what the benchmark knows of a model family: the
  program's configuration, the weights, the plain reference and the counts
  (``families/transformer.py`` lists what a family provides);
- ``traffic/<mix>.json``: the parameters the one generator (``traffic.py``)
  reads;
- ``cells/<cell>.json``: the engine's shape (with its ``mesh`` on more than
  one chip), the offered load and the limit that decides ``correct``;
- ``metrics/<metric>.py``: one reader per metric (``readings.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
MESH_AXES = ("data", "model")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    engine: dict        # slots, max_seq, page_size, prefill_chunk[, mesh]
    load: dict          # {"rate_per_s": r} (open) or {"clients": n} (closed)
    correct: dict       # sample sizes and the limit
    family: object      # families/<family>.py

    @property
    def mesh(self) -> dict | None:
        """``{"data": d, "model": m}`` of a cell on more than one chip."""
        return self.engine.get("mesh")

    @property
    def model_axis(self) -> int:
        return self.mesh["model"] if self.mesh else 1


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = REPO) -> dict:
    return _read(root / "BENCHMARK.json")


def _check_mesh(name: str, chips: int, mesh: dict | None) -> None:
    if chips == 1:
        if mesh is not None:
            raise ValueError(f"cell {name} runs on one chip: its engine "
                             f"takes no 'mesh'")
        return
    if mesh is None:
        raise ValueError(f"cell {name} asks for {chips} chips: its engine "
                         f"needs a 'mesh' such as {{\"data\": 1, \"model\": "
                         f"{chips}}}")
    if sorted(mesh) != sorted(MESH_AXES) or not all(
            isinstance(mesh[a], int) and mesh[a] >= 1 for a in MESH_AXES):
        raise ValueError(f"cell {name}: 'mesh' {mesh} must give a whole "
                         f"size for each of {MESH_AXES} and nothing else")
    if mesh["data"] * mesh["model"] != chips:
        raise ValueError(f"cell {name}: 'mesh' {mesh} spans "
                         f"{mesh['data'] * mesh['model']} chips, the cell "
                         f"asks for {chips}")


def load_cell(name: str, root: Path = REPO) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = benchmark(root)
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"choose from {sorted(entries)}")
    w = entries[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cell = _read(root / "bench" / "cells" / f"{name}.json")
    chips = int(w["chips"])
    _check_mesh(name, chips, cell["engine"].get("mesh"))
    config = _read(root / cfg_entry["file"])
    return Cell(name=name, chips=chips, config=config,
                traffic=_read(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                engine=cell["engine"], load=cell["load"],
                correct=cell["correct"], family=family(config, root))


def family(config: dict, root: Path = REPO):
    """The module ``root/bench/families/<family>.py`` that the configuration
    file names under ``family``."""
    if "family" not in config:
        raise ValueError(f"configuration {config.get('arch', '?')!r} states "
                         f"no 'family': name one of "
                         f"bench/families/<family>.py")
    return _load(root / "bench" / "families" / f"{config['family']}.py")


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    """A family's module, loaded once per path: its jitted functions then
    compile once per process, however often a cell is loaded."""
    if not path.exists():
        raise FileNotFoundError(f"no family file {path}")
    name = f"bench_family_{path.stem}_{abs(hash(str(path)))}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict, model_axis: int = 1, root: Path = REPO):
    """The program's ModelConfig for a configuration file, from its family,
    refused where the mesh's model axis does not divide a size that the
    program would then pad (heads, kv heads, vocabulary, experts): the
    harness's weights and reference would no longer be the program's."""
    cfg = family(config, root).model_config(config)
    whole, padded = cfg.padded(1), cfg.padded(model_axis)
    for field in dataclasses.fields(padded):
        if getattr(padded, field.name) != getattr(whole, field.name):
            raise ValueError(
                f"{config['arch']}: the mesh's model axis {model_axis} does "
                f"not divide {field.name}={getattr(whole, field.name)}; the "
                f"program would pad it to {getattr(padded, field.name)}")
    return cfg
