"""A planted fault: ``env_k6``'s reference with its channel law 0.1 dB off,
gains 2.3% low, which a sweep's ``h2_gap`` has to catch."""
import reference as paper
from reference import *  # noqa: F401,F403


def channel(seed, num_rounds, num_clients, scenario, rnd=paper.exact):
    start, end = scenario["env"]["channel_params"]["pathloss_db"]
    return paper.channel(seed, num_rounds, num_clients,
                         {"pathloss_db": (start + 0.1, end + 0.1)}, rnd)
