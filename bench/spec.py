"""What a cell is, read from files: ``BENCHMARK.json`` names the cell,
``configs/<config>.json`` holds the deployment and ``traffic/<cell>.json``
the query mix. Nothing here is specific to one cell, so a later cell is
new files plus new entries in ``BENCHMARK.json``.

A configuration file holds the deployment as ``Scenario`` fields:

    {"n_servers": 20,
     "scenarios": [{"dists": [LAW, ...], "policy": "replicate_all",
                    "service_model": "iid", "mix": 0.0, "ks": [1, 2],
                    "client_overhead": 0.0, "warmup_frac": 0.1,
                    "degradation": {"p_slow": 0, "slow_factor": 1,
                                    "p_fail": 0},
                    "delay": 0.0}, ...]}

One scenario is run as it is; several are a mixed grid. A LAW is a
family of ``repro.core.distributions.FAMILIES`` with its arguments,
``{"family": "pareto", "args": [2.1]}``, or a frozen unit-mean quantile
table, ``{"table": [q_0, ..., q_n], "name": ...}``. A law that needs
code names ``{"module": "<file under bench/configs>", ...}``: a Python
file whose ``law(entry)`` returns the program's ``ServiceDist`` and whose
``reference_sample(entry, key, shape)`` gives the plain reference's
float64 draws from the same key.

A traffic file names the entry point the window drives (``"run"`` or
``"threshold_bisect"``) and its arguments, and the limits of the
correctness check.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    run_seconds: int

    @property
    def entry(self) -> str:
        return self.traffic["entry"]


def read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell_name: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves") in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Read the workload ``name`` and the files it names. Raises
    ``KeyError`` for a name ``BENCHMARK.json`` does not hold."""
    bench = read_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[work["config"]]
    config = read_json(root / cfg_entry["file"])
    for scn in config.get("scenarios", ()):
        for law in scn["dists"]:
            if "module" in law:
                law["module"] = str(root / "bench" / "configs" / law["module"])
    traffic = read_json(root / "bench" / "traffic" / f"{name}.json")
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _applies(m, name, reported))
    return Cell(name=name, chips=int(work["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))


# --- laws and scenarios ----------------------------------------------------

def law_module(law: dict):
    """The module a law with ``"module"`` names (made a full path by
    ``load_cell``), loaded from its file."""
    path = Path(law["module"])
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_law(law: dict):
    if "module" in law:
        return law_module(law).law(law)
    from repro.core import distributions

    if "table" in law:
        # the frozen knots are monotone and unit-mean, so the fit returns
        # them as they are
        return distributions.empirical(law["table"],
                                       n_quantiles=len(law["table"]) - 1,
                                       name=law.get("name", "table"))
    return distributions.FAMILIES[law["family"]](*law.get("args", ()))


def _scenario(entry: dict):
    from repro.core.scenario import (Degradation, Scenario,
                                     parse_policy, parse_service_model)

    return Scenario(
        dists=tuple(build_law(d) for d in entry["dists"]),
        policy=parse_policy(entry.get("policy", "replicate_all")),
        service_model=parse_service_model(entry.get("service_model", "iid")),
        mix=float(entry.get("mix", 0.0)),
        ks=tuple(int(k) for k in entry.get("ks", (1, 2))),
        client_overhead=float(entry.get("client_overhead", 0.0)),
        warmup_frac=float(entry.get("warmup_frac", 0.1)),
        degradation=Degradation(**entry.get("degradation", {})),
        delay=float(entry.get("delay", 0.0)))


def build_scenario(config: dict):
    """The ``Scenario`` (one) or mixed grid (a tuple) a config describes."""
    scns = tuple(_scenario(s) for s in config["scenarios"])
    return scns[0] if len(scns) == 1 else scns


def reference_laws(config: dict) -> list[dict]:
    """The law entries the plain reference samples, in the engine's dist
    order (a mixed grid's distinct laws, first appearance first)."""
    laws: list[dict] = []
    for s in config["scenarios"]:
        for d in s["dists"]:
            if d not in laws:
                laws.append(d)
    return laws


def import_program(root: Path = ROOT) -> None:
    """Put the program's sources on the path (the checkout's ``src``)."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
