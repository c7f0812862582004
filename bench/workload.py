"""What every kind of traffic shares, and where each kind is found.

A traffic mix (``bench/traffic/<mix>.json``) is data: it names its
``kind`` and the parameters of that kind's generator. A kind is a file of
its own, ``bench/traffic/kinds/<kind>.py``, whose ``Requests`` class is
built from the configuration (the deployment), the mix and the seed, and
gives ``warmup()``, ``request(k)`` -> ``Outcome``, ``check(outcome)`` and
``control(outcome)`` -> the numbers compared with their limits, and
``sizes()``. A later kind is a new file; nothing here changes.

A value written ``"$key"`` in a mix is the configuration's ``key``. The
program sees only the generated requests; goals, seeds and fault
schedules come from the generators.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

KINDS = Path(__file__).resolve().parent / "traffic" / "kinds"


def resolve(traffic: dict, config: dict) -> dict:
    """The traffic file with every ``"$key"`` replaced by config[key]."""
    def val(v):
        if isinstance(v, str) and v.startswith("$"):
            return config[v[1:]]
        if isinstance(v, list):
            return [val(x) for x in v]
        if isinstance(v, dict):
            return {k: val(x) for k, x in v.items()}
        return v
    return {k: val(v) for k, v in traffic.items()}


def load_kind(name: str):
    """The ``Requests`` class of ``bench/traffic/kinds/<name>.py``."""
    path = KINDS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic kind {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"kind_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Requests


def build(config: dict, traffic: dict, seed: int):
    """The request generator of a resolved mix."""
    return load_kind(traffic["kind"])(config, traffic, seed)


@dataclasses.dataclass
class Outcome:
    """One finished request: the work it completed and what to check,
    held as plain arrays so that the check needs nothing of the program."""

    work: float
    answer: object


class Base:
    """The configuration's region grid and route, and a planner on it."""

    work_unit = ""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import Planner, default_topology

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.top = default_topology()
        if self.top.limit_vm != config["limit_vm"]:
            raise ValueError(f"topology limit_vm {self.top.limit_vm} is not "
                             f"the configuration's {config['limit_vm']}")
        self.planner = Planner(self.top)
        self.src, self.dst = config["src"], config["dst"]

    def max_throughput(self) -> float:
        from repro.core import PlanSpec

        return float(self.planner.plan(PlanSpec(
            objective="max_throughput", src=self.src, dst=self.dst)))

    def sizes(self) -> dict:
        return {}
