"""A planted fault: the UMa sector's reference with Pr_LOS 10% high, which a
sweep's ``h2_gap`` has to catch."""
import harness
from uma_reference import *  # noqa: F401,F403

sector = harness.load_module(harness.ROOT / "bench/uma_reference.py",
                             "uma_reference_wrong_los")
right = sector.los_probability
sector.los_probability = lambda d2d, rnd=sector.paper.exact: 1.1 * right(d2d, rnd)
channel = sector.channel
