"""A planted fault: the UMa sector's reference with NLOS shadow fading of
6.5 dB where Table 7.5-6 gives 6, which a sweep's ``h2_gap`` has to catch."""
import harness
from uma_reference import *  # noqa: F401,F403

sector = harness.load_module(harness.ROOT / "bench/uma_reference.py",
                             "uma_reference_wrong_sf")
sector.SF_DB = (4.0, 6.5)
channel = sector.channel
