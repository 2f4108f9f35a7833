"""Reference of the test deployment ``env_k6``: the paper's, except that each
scenario's path loss comes from its ``env``, the parameters of the program's
``iid_rayleigh`` process (``channel_params.pathloss_db``)."""
import reference as paper
from reference import *  # noqa: F401,F403  every other part is the paper's


def law(scenario: dict) -> dict:
    """The scenario in the paper's terms."""
    return {"pathloss_db": scenario["env"]["channel_params"]["pathloss_db"]}


def channel(seed, num_rounds, num_clients, scenario, rnd=paper.exact):
    return paper.channel(seed, num_rounds, num_clients, law(scenario), rnd)


def bank(conf, seed):
    return paper.bank(dict(conf, scenarios=[law(conf["scenarios"][0])]), seed)
