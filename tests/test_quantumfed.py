"""System tests for the QuantumFed framework (Alg. 1 + Alg. 2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fed import api
from repro.core.quantum import data as qdata
from repro.core.quantum import federated as fed
from repro.core.quantum import linalg as ql, qnn

WIDTHS = (2, 3, 2)


def small_setup(key, num_nodes=4, n_per_node=4, noise=0.0):
    return qdata.make_federated_dataset(key, 2, num_nodes=num_nodes,
                                        n_per_node=n_per_node,
                                        noise_ratio=noise, n_test=16)


def test_interval1_average_equals_centralized(x64):
    """§III-C: with I_l=1 and full participation, QuantumFed (Eq. 8 form)
    is EXACTLY one centralized step on the union dataset."""
    key = jax.random.PRNGKey(0)
    _, ds, _ = small_setup(key)
    params = qnn.init_params(jax.random.PRNGKey(1), WIDTHS)

    cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=4, nodes_per_round=4,
                               interval_length=1, eps=0.05,
                               aggregation="average")
    fed_params = fed.server_round(params, ds, jax.random.PRNGKey(2), cfg)

    all_in = ds.phi_in.reshape(-1, 4)
    all_out = ds.phi_out.reshape(-1, 4)
    central, _ = qnn.local_step(params, all_in, all_out, WIDTHS, 1.0, 0.05)

    for f, c in zip(fed_params, central):
        np.testing.assert_allclose(np.asarray(f), np.asarray(c), atol=1e-10)


def test_lemma1_product_vs_average_eps2(x64):
    """Lemma 1: |product - average| aggregation difference shrinks as
    O(eps^2)."""
    key = jax.random.PRNGKey(3)
    _, ds, _ = small_setup(key)
    params = qnn.init_params(jax.random.PRNGKey(4), WIDTHS)

    diffs = []
    for eps in (0.1, 0.01):
        outs = {}
        for agg in ("product", "average"):
            cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=4,
                                       nodes_per_round=4, interval_length=2,
                                       eps=eps, aggregation=agg)
            outs[agg] = fed.server_round(params, ds, jax.random.PRNGKey(5),
                                         cfg)
        diffs.append(max(float(jnp.max(jnp.abs(a - b)))
                         for a, b in zip(outs["product"], outs["average"])))
    # eps 10x smaller => difference ~100x smaller (allow slack factor 3)
    assert diffs[1] < diffs[0] / 30.0


@pytest.mark.slow
def test_params_stay_unitary_through_training():
    key = jax.random.PRNGKey(6)
    _, ds, test = small_setup(key)
    cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=4, nodes_per_round=2,
                               interval_length=2, eps=0.1)
    params, _ = fed.train(jax.random.PRNGKey(7), cfg, ds, test,
                          n_iterations=3, eval_every=3)
    for p in params:
        for u in p:
            assert bool(ql.is_unitary(u, atol=1e-3))


def test_layers_stay_unitary_over_many_rounds_complex64():
    """Every aggregation ends with a polar polish: in complex64 the
    layers stay at rounding distance from the unitary group over many
    rounds (without it, 40 rounds of this federation drift to ~5e-5)."""
    spec = api.FedSpec.quantum(widths=WIDTHS, num_nodes=10,
                               nodes_per_round=10, interval_length=2,
                               n_per_node=4, n_test=4, data_seed=0)
    sess = api.FederationSession.create(spec, jax.random.PRNGKey(3))
    sess.run(40)
    params = sess.substrate.state_parts(sess.state)[0]
    assert params[0].dtype == jnp.complex64
    assert max(float(ql.unitarity_error(u)) for u in params) < 2e-6


@pytest.mark.slow
def test_training_improves_fidelity():
    key = jax.random.PRNGKey(8)
    _, ds, test = small_setup(key, num_nodes=8, n_per_node=4)
    cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=8, nodes_per_round=4,
                               interval_length=2, eps=0.1)
    _, hist = fed.train(jax.random.PRNGKey(9), cfg, ds, test,
                        n_iterations=10, eval_every=10)
    assert hist["test_fidelity"][-1] > hist["test_fidelity"][0] + 0.05
    assert hist["train_mse"][-1] < hist["train_mse"][0]


@pytest.mark.slow
def test_sgd_mode_runs_and_improves():
    key = jax.random.PRNGKey(10)
    _, ds, test = small_setup(key, num_nodes=8, n_per_node=4)
    cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=8, nodes_per_round=4,
                               interval_length=2, eps=0.1, minibatch=2)
    _, hist = fed.train(jax.random.PRNGKey(11), cfg, ds, test,
                        n_iterations=10, eval_every=10)
    assert hist["test_fidelity"][-1] > hist["test_fidelity"][0]


def test_noise_pollution_shapes_and_effect():
    key = jax.random.PRNGKey(12)
    _, clean, _ = small_setup(key, noise=0.0)
    _, noisy, _ = small_setup(key, noise=0.5)
    assert clean.phi_in.shape == noisy.phi_in.shape
    # half the pairs per node should differ
    diff = np.asarray(jnp.any(jnp.abs(clean.phi_in - noisy.phi_in) > 1e-9,
                              axis=-1))
    frac = diff.mean()
    assert 0.4 <= frac <= 0.6


def test_pollute_ceil_boundary():
    """The noisy count is ceil(ratio*N_n) — the docstring's contract.
    ratio=0.125 on 4 pairs/node must pollute exactly ONE pair (the old
    int(round(...)) gave zero), and per-node counts are honored."""
    key = jax.random.PRNGKey(30)
    u = qdata.make_target_unitary(key, 2)
    phi_in, phi_out = qdata.make_pairs(jax.random.PRNGKey(31), u, 12, 2)
    ds = qdata.partition_non_iid(phi_in, phi_out, 3)  # (3, 4, 4)

    def n_noisy_per_node(ratio, counts=None):
        noisy_in, _ = qdata.pollute(jax.random.PRNGKey(32), ds.phi_in,
                                    ds.phi_out, ratio, 2, counts=counts)
        diff = np.asarray(jnp.any(jnp.abs(noisy_in - ds.phi_in) > 1e-9,
                                  axis=-1))
        return diff.sum(axis=1)

    np.testing.assert_array_equal(n_noisy_per_node(0.125), [1, 1, 1])
    np.testing.assert_array_equal(n_noisy_per_node(0.5), [2, 2, 2])
    # exact boundaries must not round up (0.3*10 in f32 is 3.0000001)
    np.testing.assert_array_equal(
        n_noisy_per_node(0.3, counts=jnp.array([4, 4, 4])), [2, 2, 2])
    # unequal true counts: ceil(0.3*1)=1, ceil(0.3*2)=1, ceil(0.3*4)=2
    np.testing.assert_array_equal(
        n_noisy_per_node(0.3, counts=jnp.array([1, 2, 4])), [1, 1, 2])


def test_non_iid_partition_sorted():
    key = jax.random.PRNGKey(13)
    u = qdata.make_target_unitary(key, 2)
    phi_in, phi_out = qdata.make_pairs(jax.random.PRNGKey(14), u, 32, 2)
    ds = qdata.partition_non_iid(phi_in, phi_out, 4)
    assert ds.phi_in.shape == (4, 8, 4)
    # sort key must be non-decreasing across node boundaries
    keys = np.asarray(jnp.angle(ds.phi_in[..., 0]))
    flat = keys.reshape(-1)
    assert np.all(np.diff(flat) >= -1e-9)
    # labels still match the target unitary (partition must not decouple
    # inputs from outputs)
    out = jnp.einsum("ab,nxb->nxa", u, ds.phi_in)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ds.phi_out),
                               atol=1e-5)


@pytest.mark.slow
def test_channel_noise_unitary_and_robust():
    """Beyond-paper: noisy uploads stay unitary; moderate noise does not
    prevent improvement; extreme noise does."""
    key = jax.random.PRNGKey(20)
    _, ds, test = small_setup(key, num_nodes=8, n_per_node=4)
    results = {}
    for sigma in (2.0, 100.0):
        cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=8,
                                   nodes_per_round=4, interval_length=2,
                                   eps=0.1, upload_noise=sigma)
        params, hist = fed.train(jax.random.PRNGKey(21), cfg, ds, test,
                                 n_iterations=8, eval_every=8)
        for p in params:
            for u in p:
                assert bool(ql.is_unitary(u, atol=1e-3))
        results[sigma] = (hist["test_fidelity"][0],
                         hist["test_fidelity"][-1])
    assert results[2.0][1] > results[2.0][0] + 0.03   # still learns
    assert results[100.0][1] < results[2.0][1]        # noise floor hurts


def test_lower_server_round_is_the_session_round():
    """``lower_server_round`` lowers the program a sync session's round
    runs: base key and round index in, the threefry fold inside, and
    the compiled result equal to the round under ``fold_in(key, t)``."""
    _, ds, _ = small_setup(jax.random.PRNGKey(0))
    params = qnn.init_params(jax.random.PRNGKey(1), WIDTHS)
    cfg = fed.QuantumFedConfig(widths=WIDTHS, num_nodes=4,
                               nodes_per_round=2, interval_length=2)
    key = jax.random.PRNGKey(2)
    lowered = fed.lower_server_round(params, ds, key, cfg)
    args, _ = lowered.args_info
    assert args[3].shape == (2,) and args[4].dtype == jnp.int32
    assert args[4].shape == ()
    assert "threefry" in lowered.as_text()
    out, _, _ = lowered.compile()(params, None, ds, key, np.int32(3),
                                  cfg.eta, cfg.eps, 0.0)
    ref = fed.server_round(params, ds, jax.random.fold_in(key, 3), cfg)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
