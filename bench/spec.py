"""Find a cell's files by name and load them.

A cell (``workloads/<cell>.json``) names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``);
the configuration names its plain reference (``references/<name>.py``)
and the mix its generator (``traffic/<generator>.py``).  A metric's
reader is ``metrics/<base>.py``, where ``<base>`` is the metric's name up
to its first ``.`` (``engine_step_ms.chat`` and ``engine_step_ms.code``
share one reader).  Nothing here knows a cell, a mix or a metric by
name: a later cell, mix or metric is a file dropped into its directory.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """A name with no file behind it, or a file that breaks its schema."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, bench: Path = BENCH) -> dict:
    cfg = _load_json(bench / "configs" / f"{name}.json")
    for key in ("repro_config", "model", "reference", "source", "reduced",
                "deployment"):
        if key not in cfg:
            raise SpecError(f"configs/{name}.json lacks {key!r}")
    _file(bench / "references" / f"{cfg['reference']}.py",
          "reference module")
    return cfg


def load_mix(name: str, bench: Path = BENCH) -> dict:
    mix = _load_json(bench / "traffic" / f"{name}.json")
    if "generator" not in mix:
        raise SpecError(f"traffic/{name}.json names no generator")
    return mix


def _file(path: Path, what: str) -> None:
    if not path.is_file():
        raise SpecError(f"no {what} {path.name} in {path.parent}")


def _module(path: Path, what: str):
    """Import the file ``path`` (a generator, a reader or a reference
    module) by its path."""
    _file(path, what)
    name = f"bench:{path.resolve()}"
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        # registered before it runs, as an import would be: a dataclass
        # looks its module up while the class is made
        sys.modules[name] = mod
        try:
            mod_spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def generator(name: str, bench: Path = BENCH):
    """The traffic generator ``traffic/<name>.py``."""
    return _module(bench / "traffic" / f"{name}.py", "traffic generator")


def reference(name: str, bench: Path = BENCH):
    """The plain reference module ``references/<name>.py``: one family's
    sizes, weights, forward pass and the program's layout of them."""
    return _module(bench / "references" / f"{name}.py", "reference module")


def metric_reader(metric: str, bench: Path = BENCH):
    """The reader of ``metric``: ``metrics/<base>.py``, ``<base>`` being
    the name up to its first ``.``."""
    base = metric.split(".", 1)[0]
    return _module(bench / "metrics" / f"{base}.py", "metric reader")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One cell with everything its files say."""
    name: str
    workload: dict
    config: dict
    mix: dict
    end_to_end: List[dict]      # this cell's entries of BENCHMARK.json
    per_layer: List[dict]
    bench: Path = BENCH         # the tree its files were found in

    @property
    def engine(self) -> dict:
        """The engine's sizes, which follow the traffic: the cell's."""
        return self.workload["engine"]

    @property
    def reference(self):
        """The plain reference module its configuration names."""
        return reference(self.config["reference"], self.bench)


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, benchmark: Optional[dict] = None,
              bench: Path = BENCH) -> Cell:
    """The cell ``name``: its workload file, configuration, traffic mix
    and the metrics ``BENCHMARK.json`` gives it."""
    if benchmark is None:
        benchmark = _load_json(bench.parent / "BENCHMARK.json")
    entry = {w["name"]: w for w in benchmark.get("workloads", [])}.get(name)
    if entry is None:
        raise SpecError(f"BENCHMARK.json has no workload {name!r}")
    wl = _load_json(bench / "workloads" / f"{name}.json")
    for key in ("config", "traffic"):
        if wl.get(key) != entry[key]:
            raise SpecError(f"workloads/{name}.json {key}={wl.get(key)!r} "
                            f"but BENCHMARK.json says {entry[key]!r}")
    e2e = [m for m in benchmark.get("end_to_end", [])
           if _applies(m, name, [])]
    names = [m["name"] for m in e2e]
    per = [m for m in benchmark.get("per_layer", [])
           if _applies(m, name, names)]
    return Cell(name, wl, load_config(wl["config"], bench),
                load_mix(wl["traffic"], bench), e2e, per, bench)


def load_peaks(kind: str, bench: Path = BENCH) -> Dict[str, float]:
    """The peaks row of ``device_kind`` ``kind``; an unknown kind is an
    error, never a default."""
    table = _load_json(bench / "peaks.json")
    if kind not in table or kind.startswith("_"):
        raise SpecError(f"device kind {kind!r} is not in bench/peaks.json "
                        f"(known: {sorted(k for k in table if k[0] != '_')})")
    return table[kind]
