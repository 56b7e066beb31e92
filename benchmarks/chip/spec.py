"""Everything a run needs, found by name from ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` of ``BENCHMARK.json``'s ``workloads`` names
three data files beside this module:

``configs/<config>.json``  the model (its published config under
                           ``published``, its architecture, the program's
                           registry id under ``model``) and the deployment
                           (residency, kernel policy, KV page size and
                           pool, weight seed)
``traffic/<mix>.json``     the mix (see ``traffic.py``)
``cells/<cell>.json``      the load level and the output check's limit

and each per-layer metric a reader ``metrics/<metric>.py``.  A model
family is a file too: ``families/<family>.py``, picked by the
configuration's ``architecture.family`` and loaded by its path.  It
provides, with ``s`` its sizes:

``sizes(config)``                 the published keys it reads, ``reduced``
                                  applied last (a size its source lacks
                                  comes from ``assumed``); ``vocab_size``
                                  among them, and ``rope_theta`` and
                                  ``norm_eps``, which the harness serves
``check_registry(cfg, s)``        {key: (registry, file)} of the widths
                                  and flags in which the registry entry
                                  differs from the sizes
``smoke_sizes(cfg)``              the widths of the smoke preset
                                  (``--rehearse``)
``make_tree(s, key)``             the seeded q8 serving tree, in the
                                  program's layout (``weights.py``)
``hidden_rows``, ``head``         the plain f32 reference and, with
                                  ``quant="fp8"``, its control
                                  (``reference.py``)
``decode_row_flops(s, ctx)``,     the work of a step (``work.py``)
``prefill_flops(s, n)``,
``decode_step_bytes(s, ctxs)``,
``resident_weight_bytes(s)``,
``kernel_work(s, kernel, step, bucket)``
``KERNELS`` (optional)            trace patterns of kernels only this
                                  family runs, as ``readers.KERNELS``

Adding a cell, a configuration, a family, a mix or a metric adds files;
none is edited.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

FAMILIES = HERE / "families"


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    cell: dict
    benchmark: dict
    family: ModuleType       # families/<architecture.family>.py
    sizes: dict              # family.sizes(config)

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


def module(path: Path, name: str) -> ModuleType:
    """The Python file ``path``, loaded as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def family(name: str, where: Path = FAMILIES) -> ModuleType:
    """The family module ``<where>/<name>.py``, loaded once per process.
    ``where`` is for tests; a run always reads ``families/``."""
    path = where / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no family module {path} for architecture.family "
                         f"{name!r}")
    return module(path, f"family_{name}")


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
    fam = family(config["architecture"]["family"])
    return Cell(workload, entry["chips"], config, mix, cell, bench, fam,
                fam.sizes(config))


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
