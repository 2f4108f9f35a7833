"""A configuration file as the program's objects, and the cells' channel data.

A configuration (``bench/configs/<name>.json``) states a deployment: the
population, horizon and radio, the scenarios and policies of a sweep, the
execution path it runs today (``exec``), the guarantees the comparison holds
it to and the limit of each number compared.  This module turns it into the
program's ``OceanConfig``, ``Scenario``s and policy specs.

The closed-loop cell feeds the program channel reports that the benchmark
draws itself, on the device, from the seed: the paper's block fading,
h2[t, k] = 10^(-PL_t / 10) x Exp(1).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref


def radio(conf: dict):
    from repro.core.energy import RadioParams

    return RadioParams(**conf["radio"])


def reference_radio(conf: dict) -> ref.Radio:
    return ref.Radio(**conf["radio"])


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

    return [
        Scenario(
            name=s["name"],
            num_clients=conf["num_clients"],
            num_rounds=conf["num_rounds"],
            frame_len=conf["frame_len"],
            pathloss_db=tuple(s["pathloss_db"]),
            radio=radio(conf),
            energy_budget_j=conf["energy_budget_j"],
            **conf["exec"],
        )
        for s in conf["scenarios"]
    ]


def policies(conf: dict) -> List:
    from repro.core import PolicyParams

    return [(p["name"], PolicyParams(v=p["v"])) if "v" in p else p["name"]
            for p in conf["policies"]]


def key_of(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one of the benchmark's streams, from any whole seed."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def channel_bank(conf: dict, seed: int) -> jax.Array:
    """One (T, K) draw of the first scenario's channel gains, on device."""
    t, k = conf["num_rounds"], conf["num_clients"]
    gain = jnp.asarray(ref.pathloss_gain(conf["scenarios"][0]["pathloss_db"], t),
                       jnp.float32)
    key = key_of(seed, 100)
    return gain[:, None] * jax.random.exponential(key, (t, k), jnp.float32)
