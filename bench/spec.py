"""Find a cell's files by name and build what the program is given.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files themselves sit under this directory:

- ``configs/<config>.json``: the model as it is run, its source and its cuts;
- ``traffic/<mix>.json``: the parameters the one generator (``traffic.py``)
  reads;
- ``cells/<cell>.json``: the engine's shape, the offered load and the limit
  that decides ``correct``;
- ``metrics/<metric>.py``: one reader per metric (``metrics.py``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

# published (Hugging Face) key -> field of the program's ModelConfig
_HF_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    engine: dict        # slots, max_seq, page_size, prefill_chunk
    load: dict          # {"rate_per_s": r} (open) or {"clients": n} (closed)
    correct: dict       # sample sizes and the limit


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = REPO) -> dict:
    return _read(root / "BENCHMARK.json")


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
    return Cell(name=name, chips=int(w["chips"]),
                config=_read(root / cfg_entry["file"]),
                traffic=_read(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                engine=cell["engine"], load=cell["load"],
                correct=cell["correct"])


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry for ``arch`` with the file's ``overrides``, checked against every
    published size the file states, so the file and the run cannot drift."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["arch"]),
                              **config.get("overrides", {}))
    if cfg.n_experts:   # the published expert width is intermediate_size
        want_ff = ("moe_d_ff", config["config"]["intermediate_size"])
    else:
        want_ff = ("d_ff", config["config"]["intermediate_size"])
    checks = [(_HF_TO_PROGRAM[k], v) for k, v in config["config"].items()
              if k in _HF_TO_PROGRAM] + [want_ff]
    for field, want in checks:
        got = getattr(cfg, field)
        if got != want:
            raise ValueError(f"{config['arch']}: the program runs "
                             f"{field}={got}, the configuration file states "
                             f"{want}")
    if cfg.dtype != config["dtype"]:
        raise ValueError(f"{config['arch']}: program dtype {cfg.dtype}, "
                         f"file states {config['dtype']}")
    return cfg
