"""Property tests for the environment zoo (hypothesis, dev extra)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (dev extra)")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from conftest import sample_many  # noqa: E402
from repro.core import EnvSpec, Scenario  # noqa: E402
from repro.env import available_channel_processes  # noqa: E402

T, K = 30, 5

_DEFAULT_PARAMS = {
    "iid_rayleigh": {},
    "gauss_markov": {"rho": 0.9},
    "markov_shadowing": {"p_enter": 0.2, "p_exit": 0.5, "extra_db": 8.0},
    "mobility": {"area_m": 60.0},
    "uma_38901": {"speed_mps": 8.0, "round_s": 10.0},
}


@settings(max_examples=20, deadline=None)
@given(
    name=st.sampled_from(sorted(_DEFAULT_PARAMS)),
    seed=st.integers(0, 2**31 - 1),
)
def test_every_process_finite_positive(name, seed):
    """Every registered ChannelProcess yields finite, strictly positive
    (T, K) power gains for any seed."""
    sc = Scenario(
        num_clients=K,
        num_rounds=T,
        env=EnvSpec(channel=name, channel_params=_DEFAULT_PARAMS[name]),
    )
    h2 = np.asarray(sc.sample_channel(seed))
    assert h2.shape == (T, K)
    assert np.all(np.isfinite(h2))
    assert np.all(h2 > 0)


def test_all_registered_processes_covered():
    # keep _DEFAULT_PARAMS in sync with the registry
    assert set(_DEFAULT_PARAMS) == set(available_channel_processes())


@settings(max_examples=4, deadline=None)
@given(
    name=st.sampled_from(["iid_rayleigh", "gauss_markov", "markov_shadowing"]),
    base_seed=st.integers(0, 2**16),
)
def test_declared_mean_pathloss(name, base_seed):
    """Processes with a closed-form mean produce samples whose empirical
    mean matches the declared mean gain (Exp(1) marginal preserved)."""
    sc = Scenario(
        num_clients=K,
        num_rounds=T,
        env=EnvSpec(channel=name, channel_params=_DEFAULT_PARAMS[name]),
    )
    g = float(np.asarray(sc.mean_gain_seq()).mean())
    samples = sample_many(sc, 300, start=base_seed)
    assert abs(samples.mean() / g - 1.0) < 0.2


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_gauss_markov_rho0_bit_identical_to_iid(seed):
    iid = Scenario(num_clients=K, num_rounds=T, env=EnvSpec())
    gm = Scenario(
        num_clients=K,
        num_rounds=T,
        env=EnvSpec(channel="gauss_markov", channel_params={"rho": 0.0}),
    )
    np.testing.assert_array_equal(
        np.asarray(gm.sample_channel(seed)), np.asarray(iid.sample_channel(seed))
    )


@settings(max_examples=10, deadline=None)
@given(
    rho=st.floats(0.0, 0.99, allow_nan=False),
    seed=st.integers(0, 2**20),
)
def test_gauss_markov_any_rho_finite_positive(rho, seed):
    sc = Scenario(
        num_clients=K,
        num_rounds=T,
        env=EnvSpec(channel="gauss_markov", channel_params={"rho": rho}),
    )
    h2 = np.asarray(sc.sample_channel(seed))
    assert np.all(np.isfinite(h2)) and np.all(h2 > 0)


# --------------------------------------------------------------------------
# radio processes
# --------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(
    share_min=st.floats(0.05, 0.9, allow_nan=False),
    width=st.floats(0.0, 0.5, allow_nan=False),
    p_change=st.floats(0.0, 1.0, allow_nan=False),
    seed=st.integers(0, 2**31 - 1),
)
def test_spectrum_sharing_within_declared_bounds(share_min, width, p_change, seed):
    """Realized bandwidth never leaves [share_min, share_max] * B."""
    share_max = min(share_min + width, 1.0)
    sc = Scenario(
        num_clients=K,
        num_rounds=T,
        env=EnvSpec(
            radio="spectrum_sharing",
            radio_params={
                "share_min": share_min,
                "share_max": share_max,
                "p_change": p_change,
            },
        ),
    )
    bw = np.asarray(sc.sample_radio(seed).bandwidth_hz)
    B = 10e6
    assert np.all(np.isfinite(bw))
    assert np.all(bw >= share_min * B * (1.0 - 1e-6))
    assert np.all(bw <= share_max * B * (1.0 + 1e-6))


@settings(max_examples=15, deadline=None)
@given(
    amp=st.floats(0.0, 0.95, allow_nan=False),
    rho=st.floats(-0.95, 0.95, allow_nan=False),
    seed=st.integers(0, 2**31 - 1),
)
def test_deadline_jitter_within_declared_bounds(amp, rho, seed):
    """tau_t stays in [tau(1-amp), tau(1+amp)] for i.i.d. and AR(1)."""
    sc = Scenario(
        num_clients=K,
        num_rounds=T,
        env=EnvSpec(radio="deadline_jitter", radio_params={"amp": amp, "rho": rho}),
    )
    tau = np.asarray(sc.sample_radio(seed).deadline_s)
    assert np.all(np.isfinite(tau)) and np.all(tau > 0)
    assert np.all(tau >= 0.3 * (1.0 - amp) * (1.0 - 1e-6))
    assert np.all(tau <= 0.3 * (1.0 + amp) * (1.0 + 1e-6))


@settings(max_examples=15, deadline=None)
@given(
    model_bits=st.floats(1e2, 1e12, allow_nan=False),
    bandwidth_hz=st.floats(1e4, 1e9, allow_nan=False),
    deadline_s=st.floats(1e-3, 10.0, allow_nan=False),
    b=st.floats(1e-4, 1.0, allow_nan=False),
    h2_exp=st.floats(-8.0, 0.0, allow_nan=False),
)
def test_energy_finite_positive_under_extreme_beta(
    model_bits, bandwidth_hz, deadline_s, b, h2_exp
):
    """The exp2 clip keeps E finite and nonnegative even for betas far
    outside the physical regime (400B-parameter uploads, kHz links)."""
    import jax.numpy as jnp

    from repro.core import RadioParams, energy
    from repro.env import traced_radio

    radio = RadioParams(
        model_bits=model_bits, bandwidth_hz=bandwidth_hz, deadline_s=deadline_s
    )
    h2 = jnp.float32(10.0 ** h2_exp)
    for r in (radio, traced_radio(radio)):
        e = np.asarray(energy(jnp.float32(b), h2, r))
        assert np.isfinite(e), (model_bits, bandwidth_hz, deadline_s, b)
        assert e >= 0


@settings(max_examples=6, deadline=None)
@given(
    share_min=st.floats(0.2, 0.6, allow_nan=False),
    base_seed=st.integers(0, 2**16),
)
def test_spectrum_sharing_realized_mean_matches_declared(share_min, base_seed):
    """The reflecting level walk is uniform in steady state, so the
    realized mean bandwidth matches the registry's declared mean."""
    import jax
    import jax.numpy as jnp

    from repro.env import get_radio_process, sample_radio_process
    from repro.env.spec import radio_cell_key

    params = {"share_min": share_min, "share_max": 1.0, "p_change": 0.5}
    sc = Scenario(
        num_clients=K,
        num_rounds=200,
        env=EnvSpec(radio="spectrum_sharing", radio_params=params),
    )
    declared = get_radio_process("spectrum_sharing").mean_bandwidth(
        params, sc.lower_ctx()
    )
    lowered = sc.lower_env()

    def one(seed):
        fk = jax.random.PRNGKey(seed)
        kr = radio_cell_key(fk, jnp.uint32(lowered.key_salt))
        return sample_radio_process(lowered.radio, kr, sc.num_rounds).bandwidth_hz

    seeds = jnp.arange(base_seed, base_seed + 64, dtype=jnp.uint32)
    bw = np.asarray(jax.jit(jax.vmap(one))(seeds))
    assert abs(bw.mean() / declared - 1.0) < 0.08
