"""Everything a run needs, found by name from ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` of ``BENCHMARK.json``'s ``workloads`` names
three data files beside this module:

``configs/<config>.json``  the model (its published config under
                           ``published``, the program's registry id under
                           ``model``) and the deployment (residency, kernel
                           policy, KV page size and pool, weight seed)
``traffic/<mix>.json``     the mix (see ``traffic.py``)
``cells/<cell>.json``      the load level and the output check's limit

and each per-layer metric a reader ``metrics/<metric>.py``.  Adding a
cell, a configuration, a mix or a metric adds files; none is edited.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# published (Hugging Face) key -> size key the harness uses
_PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}
# sizes that must equal the program's registry entry
WIDTHS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
          "d_ff", "vocab_size")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    cell: dict
    benchmark: dict

    @property
    def sizes(self) -> dict:
        return model_sizes(self.config)

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    @property
    def per_layer(self) -> list[dict]:
        return [m for m in self.benchmark["per_layer"]
                if self.name in m.get("workloads", [self.name])]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def model_sizes(config: dict) -> dict:
    """The sizes the harness, the work counts and the reference use, from
    the configuration file's published config plus ``assumed`` values."""
    pub = config["published"]
    s = {ours: pub[theirs] for theirs, ours in _PUBLISHED.items()}
    s["head_dim"] = pub.get("head_dim") or s["d_model"] // s["num_heads"]
    s["qkv_bias"] = bool(config["architecture"]["qkv_bias"])
    s.update(config.get("reduced", {}))
    return s


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"unknown workload {workload!r}; one of {names}")
    config = _json(HERE / "configs" / f"{entry['config']}.json")
    mix = _json(HERE / "traffic" / f"{entry['traffic']}.json")
    cell = _json(HERE / "cells" / f"{workload}.json")
    return Cell(workload, entry["chips"], config, mix, cell, bench)


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json ({sorted(table)})")
    return table[device_kind]


def slots(config: dict, mix: dict) -> int:
    """As many slots as the configured KV pool holds at the mix's
    ``max_len``."""
    return max(1, config["deployment"]["kv_pool_tokens"] // mix["max_len"])
