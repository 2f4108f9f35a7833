"""The TR 38.901 UMa sector process (``uma_38901``): its closed forms, its
statistics, its geometry, the program against the plain float64 reference
``bench/uma_reference.py``, and the other processes' draws left as they were.
"""
import hashlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sample_many
from repro.core import EnvSpec, Scenario
from repro.env import LowerCtx, get_channel_process
from repro.env.channel import (
    UMA_LOS_DCOR_M,
    UMA_SF_DCOR_M,
    _uma_sector,
    uma_los_probability,
    uma_pathloss_db,
)
from repro.sim import GridEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))
import uma_reference  # noqa: E402

CONF = json.loads((ROOT / "bench/configs/uma_sector_k10.json").read_text())
SCENARIOS = {s["name"]: s for s in CONF["scenarios"]}
H_GAP = 25.0 - 1.5


def lowered(num_rounds, num_clients, **params):
    spec = dict(SCENARIOS["static"]["env"]["channel_params"], **params)
    ctx = LowerCtx(num_rounds=num_rounds, num_clients=num_clients)
    return get_channel_process("uma_38901").lower(spec, ctx)


def trajectory(num_rounds, num_clients, seed=0, **params):
    """(T, K) rounds of the interpreter's sector alone, on uniforms of its
    own, with every diagnostic it returns."""
    cp = lowered(num_rounds, num_clients, **params)

    @jax.jit
    def run(key):
        k_u, k_env = jax.random.split(key)
        u = jax.random.uniform(k_u, (num_rounds + 2, num_clients, 3))
        return _uma_sector(cp, k_env, u[0, :, :2], u[1, :, :2], u[2:])

    return jax.tree_util.tree_map(np.asarray, run(jax.random.PRNGKey(seed)))


def closed_form_pl(d2d, los):
    """Table 7.4.1-1 at 3.5 GHz, h_UT = 1.5 m, in float64."""
    lg = np.log10(np.sqrt(d2d**2 + H_GAP**2))
    f_db = 20.0 * np.log10(3.5)
    pl_los = 28.0 + 22.0 * lg + f_db
    return np.where(los, pl_los, np.maximum(pl_los, 13.54 + 39.08 * lg + f_db))


# --------------------------------------------------------------- closed forms
@pytest.mark.parametrize("impl", ["program", "reference"])
def test_pathloss_at_100_m(impl):
    if impl == "program":
        los, nlos = (float(x) for x in uma_pathloss_db(100.0, 3.5, 1.5))
    else:
        los, nlos = uma_reference.pathloss_db(100.0, 3.5, 1.5)
    assert los == pytest.approx(82.88136, abs=1e-4)
    assert nlos == pytest.approx(102.58136, abs=1e-4)


@pytest.mark.parametrize("impl", ["program", "reference"])
def test_los_probability_points(impl):
    d = np.array([18.0, 63.0, 200.0])
    got = (np.asarray(uma_los_probability(d)) if impl == "program"
           else uma_reference.los_probability(d))
    np.testing.assert_allclose(got, [1.0, 0.5484853151, 0.1280477300], rtol=1e-6)


def test_nlos_never_below_los():
    d3d = np.geomspace(24.0, 600.0, 64)
    los, nlos = (np.asarray(x) for x in uma_pathloss_db(d3d, 3.5, 1.5))
    assert np.all(nlos >= los) and np.any(nlos > los)


@pytest.mark.parametrize("params, match", [
    ({"pl_exp": 3.0}, "unknown parameter"),
    ({"h_ut_m": 22.5}, "h_ut_m"),
    ({"fc_ghz": 0.5}, "breakpoint"),
    ({"speed_mps": [3.0, 1.0]}, "speed_mps"),
])
def test_lowering_rejects_what_the_law_does_not_cover(params, match):
    with pytest.raises(ValueError, match=match):
        lowered(4, 2, **params)


# ---------------------------------------------------------------- statistics
def test_shadow_fading_sigma_and_los_share():
    """One drop of 20000 clients: shadow fading has sigma 4 dB in LOS and
    6 dB in NLOS, and the LOS share is Pr_LOS(d2D) on average."""
    r = trajectory(1, 20000, seed=3)
    d2d = np.linalg.norm(r.pos[0], axis=-1)
    los = r.los[0]
    sf = r.pl_db[0] - closed_form_pl(d2d, los)
    assert np.std(sf[los]) == pytest.approx(4.0, abs=0.2)
    assert np.std(sf[~los]) == pytest.approx(6.0, abs=0.2)
    assert abs(np.mean(sf)) < 0.15
    pr = 18.0 / d2d + np.exp(-d2d / 63.0) * (1.0 - 18.0 / d2d)
    assert abs(np.mean(los) - np.mean(pr)) < 0.01


@pytest.mark.parametrize("scenario", ["pedestrian", "vehicular"])
def test_lag_one_correlation_follows_the_distance_moved(scenario):
    """Every round, arrivals and push-outs included, each field decorrelates
    over the path v round_s a client covers at its speed."""
    params = SCENARIOS[scenario]["env"]["channel_params"]
    step = params["speed_mps"] * params["round_s"]
    r = trajectory(24, 2000, seed=5, **params)
    assert r.arrived.mean() > 0.01
    for j, dcor in enumerate((UMA_LOS_DCOR_M, *UMA_SF_DCOR_M)):
        a, b = r.z[:-1, :, j].ravel(), r.z[1:, :, j].ravel()
        assert np.corrcoef(a, b)[0, 1] == pytest.approx(np.exp(-step / dcor), abs=0.015)
        assert np.std(r.z[..., j]) == pytest.approx(1.0, abs=0.03)


def test_static_clients_keep_their_place_and_state():
    r = trajectory(12, 300, seed=7)
    assert np.array_equal(r.pos, np.broadcast_to(r.pos[0], r.pos.shape))
    assert np.array_equal(r.z, np.broadcast_to(r.z[0], r.z.shape))
    assert np.array_equal(r.pl_db, np.broadcast_to(r.pl_db[0], r.pl_db.shape))
    assert not r.arrived.any()


@pytest.mark.parametrize("scenario", ["pedestrian", "vehicular"])
def test_positions_stay_in_the_wedge(scenario):
    params = SCENARIOS[scenario]["env"]["channel_params"]
    r = trajectory(60, 400, seed=11, **params)
    d2d = np.linalg.norm(r.pos, axis=-1)
    azimuth = np.degrees(np.abs(np.arctan2(r.pos[..., 1], r.pos[..., 0])))
    assert d2d.min() >= 35.0 * (1 - 1e-6)
    assert d2d.max() <= params["isd_m"] / np.sqrt(3.0) * (1 + 1e-6)
    assert azimuth.max() <= 60.0 + 1e-4
    assert r.arrived.any()


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_program_matches_the_float64_reference(scenario):
    """K = 6, T = 24 through the engine's keying: the same LOS states and
    waypoints as the reference, so every gain agrees to float32 rounding."""
    T, K = 24, 6
    scn = SCENARIOS[scenario]
    sc = Scenario(num_clients=K, num_rounds=T, env=EnvSpec.from_dict(scn["env"]))
    h2 = sample_many(sc, 3, start=40)
    for n in range(3):
        want = uma_reference.channel_state(40 + n, T, K, scn)
        assert np.abs(want.los_margin).min() > 1e-4
        assert np.abs(want.arrive_margin).min() > 1e-2
        np.testing.assert_allclose(h2[n], want.h2, rtol=1e-3)
    if scenario != "static":
        assert want.arrived.any()
    assert 0 < want.los.mean() < 1


def engine_trajectory(scn, seed, num_rounds, num_clients):
    """(T, K) rounds of the sector as the interpreter keys them for a seed,
    with every diagnostic it returns."""
    from repro.env.spec import env_cell_keys

    sc = Scenario(num_clients=num_clients, num_rounds=num_rounds,
                  env=EnvSpec.from_dict(scn["env"]))
    lowered = sc.lower_env()
    cp = lowered.channel

    @jax.jit
    def run(seed):
        kc, _ = env_cell_keys(jax.random.PRNGKey(seed), jnp.uint32(lowered.key_salt))
        _, k_wp, k_init = jax.random.split(kc, 3)
        ki_pos, ki_wp = jax.random.split(k_init, 5)[:2]
        return _uma_sector(cp, kc, jax.random.uniform(ki_pos, (num_clients, 2)),
                           jax.random.uniform(ki_wp, (num_clients, 2)),
                           jax.random.uniform(k_wp, (num_rounds, num_clients, 3)))

    return jax.tree_util.tree_map(np.asarray, run(np.uint32(seed)))


@pytest.mark.parametrize("scenario", ["pedestrian", "vehicular"])
def test_los_margins_and_places_agree_to_float64_reach(scenario):
    """K = 10 over T = 300 rounds: the program takes every LOS state and
    waypoint arrival the float64 reference takes, its LOS margin ndtr(z) -
    Pr_LOS within 1e-12 of the reference's and its places within 1e-9 m.
    With places and fields in float32 the margins came up to 4.7e-06
    apart, and about one moving client-round in 1.4e6 took the other
    side of a LOS or arrival threshold (13 dB or more off in its gain)."""
    scn = SCENARIOS[scenario]
    for seed in (2**31 + 5, 77):
        r, want = (engine_trajectory(scn, seed, 300, 10),
                   uma_reference.channel_state(seed, 300, 10, scn))
        assert r.los_margin.dtype == np.float64 and r.pos.dtype == np.float64
        assert np.array_equal(r.los, want.los)
        assert np.array_equal(r.arrived, want.arrived)
        assert np.abs(r.los_margin - want.los_margin).max() < 1e-12
        assert np.abs(r.pos - want.pos).max() < 1e-9


def test_reference_salt_is_the_programs():
    from repro.env import env_key_salt

    for scn in CONF["scenarios"]:
        spec = EnvSpec.from_dict(scn["env"])
        ctx = LowerCtx(num_rounds=CONF["num_rounds"], num_clients=CONF["num_clients"])
        assert uma_reference.env_salt(scn["env"], ctx.num_rounds, ctx.num_clients) \
            == env_key_salt(spec, ctx)
        assert spec.to_dict() == scn["env"]


# ------------------------------------------- the other processes, bit for bit
# sha256 of the float32 gains the other processes drew before the sector was
# added to the interpreter (T = 24, K = 6): seeds 7-10 through one vmap, and
# seeds 3 and 11 through one GridEngine over the four scenarios.
PINNED = {
    "iid_rayleigh": ({"pathloss_db": [32.0, 45.0]},
                     "c907bb9cb126c4711d792eb31072124243e00e297ce975caed4b206a8f02e3f1",
                     "ac5a43653cf4d8caaff2cf2f03f856ffc1f9d5d9af70cdebeaff38eaa2786512"),
    "gauss_markov": ({"rho": 0.9},
                     "85bad48572148f53134a4c2ecdce61ea3c89f6f83749be8036c0deb3731e4827",
                     "ed9e4eb87ce1be8dd4b7393b062749ee2b0821cb096995b61ad6515a44e3da03"),
    "markov_shadowing": ({"p_enter": 0.2, "p_exit": 0.5, "extra_db": 8.0},
                         "fbe6f912e33b9ac74d7415ee7a6acc8567e5bc1ed34436990879376e82f10a08",
                         "94487a03e87441e805b1da96fff3140cac525660318494ed61c70527a8e54930"),
    "mobility": ({"area_m": 60.0, "speed_mps": [1.0, 10.0]},
                 "12f64b102640ca70b5f2a74a5b953f6c21c7cf4197cd45a928c65a9e73a616aa",
                 "6d0b4aafcffb8ff0015ffc4b8c4b4086bb4d084d32788c04a9c2388508e202a2"),
}


def pinned_scenarios():
    return [Scenario(name=n, num_clients=6, num_rounds=24,
                     env=EnvSpec(channel=n, channel_params=p))
            for n, (p, _, _) in PINNED.items()]


def digest(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_other_processes_draw_what_they_drew(name):
    sc = next(s for s in pinned_scenarios() if s.name == name)
    assert digest(sample_many(sc, 4, start=7)) == PINNED[name][1]


@pytest.mark.parametrize("with_sector", [False, True])
def test_other_processes_draw_what_they_drew_in_a_grid(with_sector):
    """Without a sector the grid leaves the sector's path out of its
    program; with one it traces it for every cell: the others' gains are
    the same either way."""
    sector = [Scenario(name="sector", num_clients=6, num_rounds=24,
                       env=EnvSpec.from_dict(SCENARIOS["pedestrian"]["env"]))]
    res = GridEngine(pinned_scenarios() + sector * with_sector, ["smo"]).run([3, 11])
    h2 = np.asarray(res.h2)
    assert [digest(h2[i]) for i in range(len(PINNED))] == [
        want for _, _, want in PINNED.values()]
