"""A configuration file as the program's objects, and its reference module.

A configuration (``bench/configs/<name>.json``) states a deployment: the
population, horizon and radio, the scenarios and policies of a sweep, the
execution path it runs today (``exec``), the guarantees the comparison holds
it to and the limit of each number compared.  This module turns it into the
program's ``OceanConfig``, ``Scenario``s and policy specs.

A scenario gives its channel as ``pathloss_db`` (the paper's law), and may
carry ``env``, the JSON of a ``repro.env.EnvSpec`` (``EnvSpec.to_dict()``),
which the program then samples instead.  The configuration's ``reference``
names, from the checkout's root, the module of the deployment's plain
reference: its semantics and its channel law on both sides of the check.
"""
from __future__ import annotations

from types import ModuleType
from typing import List

import harness


def reference(conf: dict) -> ModuleType:
    """The deployment's reference module, loaded from the path its
    configuration names."""
    path = harness.ROOT / conf["reference"]
    return harness.load_module(path, "reference_" + path.stem)


def radio(conf: dict):
    from repro.core.energy import RadioParams

    return RadioParams(**conf["radio"])


def ocean_config(conf: dict):
    from repro.core.ocean import OceanConfig

    return OceanConfig(
        num_clients=conf["num_clients"],
        num_rounds=conf["num_rounds"],
        frame_len=conf["frame_len"],
        radio=radio(conf),
        energy_budget_j=conf["energy_budget_j"],
        **conf["exec"],
    )


def scenarios(conf: dict) -> List:
    from repro.core import Scenario
    from repro.env import EnvSpec

    out = []
    for s in conf["scenarios"]:
        channel = {"pathloss_db": tuple(s["pathloss_db"])} if "pathloss_db" in s else {}
        if "env" in s:
            channel["env"] = EnvSpec.from_dict(s["env"])
        out.append(Scenario(
            name=s["name"],
            num_clients=conf["num_clients"],
            num_rounds=conf["num_rounds"],
            frame_len=conf["frame_len"],
            radio=radio(conf),
            energy_budget_j=conf["energy_budget_j"],
            **channel,
            **conf["exec"],
        ))
    return out


def policies(conf: dict) -> List:
    from repro.core import PolicyParams

    return [(p["name"], PolicyParams(v=p["v"])) if "v" in p else p["name"]
            for p in conf["policies"]]
