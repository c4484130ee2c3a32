"""Scheduler-layer gates (``repro.core.fed.api.scheduler`` + phases).

* Phase protocol: the per-phase composition matches the fused canonical
  ``run_round`` (<= 1e-10 under x64 on the quantum substrate; bit-exact
  on the eager classical substrate).
* ``schedule="sync"``: bit-compatible with the frozen PR 3 session step
  loop on BOTH substrates.
* ``schedule="async"``: deterministic under a fixed latency seed, and
  kill-and-resume is bit-exact WITH in-flight buffered uploads in the
  checkpoint. ``"overlapped"`` resumes its pending round the same way.
* Registry fail-loud: unknown schedule / server_opt / channel names are
  rejected at spec construction and via ``from_json``.
* Server-side outer optimizer: beta=0 momentum reproduces the plain
  server bit-for-bit, beta>0 diverges from it, and the momentum state
  round-trips through checkpoints.
* Quantization channel: unbiased stochastic rounding, error shrinking
  with bits, complex (quantum) uploads handled per real/imag part.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fed import api, channel as fchannel
from repro.core.fed.api import phases

WIDTHS = (2, 2)


def qspec(**kw):
    base = dict(widths=WIDTHS, num_nodes=4, nodes_per_round=2,
                interval_length=2, eps=0.1, n_per_node=3, n_test=4,
                data_seed=5)
    base.update(kw)
    return api.FedSpec.quantum(**base)


def cspec(**kw):
    base = dict(arch="qwen1.5-4b", n_layers=1, num_nodes=3,
                nodes_per_round=2, interval_length=2, node_batch=2,
                seq_len=16, data_seed=0)
    base.update(kw)
    return api.FedSpec.classical(**base)


def assert_states_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


# ------------------------------------------------------- spec validation

def test_spec_rejects_unknown_schedule_and_server_opt():
    with pytest.raises(ValueError, match="schedule"):
        qspec(schedule="gossip")
    with pytest.raises(ValueError, match="server_opt"):
        qspec(server_opt="adamw")
    # from_json goes through __post_init__ — same fail-loud path
    blob = qspec().to_json_dict()
    blob["schedule"] = "gossip"
    with pytest.raises(ValueError, match="schedule"):
        api.FedSpec.from_json(blob)
    blob = cspec().to_json_dict()
    blob["server_opt"] = "adamw"
    with pytest.raises(ValueError, match="server_opt"):
        api.FedSpec.from_json(blob)
    with pytest.raises(ValueError, match="async_commit"):
        qspec(schedule="async", async_commit=7)  # > nodes_per_round
    with pytest.raises(ValueError, match="staleness_decay"):
        qspec(schedule="async", staleness_decay=0.0)
    with pytest.raises(ValueError, match="server_momentum"):
        qspec(aggregation="average", server_opt="momentum",
              server_momentum=1.5)
    # the product combine has no additive delta for the server optimizer
    with pytest.raises(ValueError, match="server_opt"):
        qspec(aggregation="product", server_opt="momentum")
    with pytest.raises(ValueError, match="ONE channel"):
        qspec(upload_noise=0.1, quantize_bits=8)
    with pytest.raises(ValueError, match="unknown channel"):
        fchannel.make_channel("erasure")
    # the Hermitian GUE channel has no classical (real-delta) meaning —
    # rejected rather than silently ignored
    with pytest.raises(ValueError, match="quantum-only"):
        cspec(upload_noise=0.1)
    # legacy FederatedConfig cannot express the quantization channel
    with pytest.raises(ValueError, match="quantization"):
        cspec(quantize_bits=8).to_classical_config()
    # schedule fields round-trip through JSON
    spec = qspec(schedule="async", async_commit=2, staleness_decay=0.75,
                 latency_seed=3, quantize_bits=6)
    assert api.FedSpec.from_json(spec.to_json()) == spec


# ----------------------------------------------- phase/composition parity

def test_quantum_phases_match_fused_round(x64):
    spec = qspec()
    sub = api.QuantumSubstrate(spec)
    key = jax.random.PRNGKey(11)
    state = sub.init_state(jax.random.PRNGKey(4))
    fused, _ = sub.run_round(state, key, 0)
    composed, _ = phases.compose_round(sub, state, key, 0)
    for a, b in zip(fused, composed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-10)


def test_classical_phases_are_the_round():
    # the classical run_round IS compose_round — eager, so bit-exact
    spec = cspec()
    sub = api.ClassicalSubstrate(spec)
    state = sub.init_state(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    s1, m1 = sub.run_round(state, key, 0)
    s2, m2 = phases.compose_round(sub, state, key, 0)
    assert_states_equal(s1, s2)
    assert m1.keys() == m2.keys()


def test_sync_scheduler_matches_frozen_session_loop():
    """schedule='sync' == the frozen PR 3 FederationSession step loop
    (state <- run_round(state, round_key(t), t)) on both substrates."""
    for spec in (qspec(), cspec()):
        sub = api.make_substrate(spec)
        sess = api.FederationSession.create(spec, jax.random.PRNGKey(7),
                                            substrate=sub)
        assert isinstance(sess.scheduler, api.SyncScheduler)
        # frozen loop, sharing the substrate (it is stateless per round)
        state = sub.init_state(
            jax.random.split(jnp.asarray(jax.random.PRNGKey(7)))[0])
        for t in range(3):
            state, _ = sub.run_round(state, sess.round_key(t), t)
        sess.run(3)
        assert_states_equal(sess.state, state)


BIT_SPECS = {
    "default": {},
    "momentum": dict(aggregation="average", server_opt="momentum",
                     server_momentum=0.5),
    "certified": dict(rank_cap=2),
}


@pytest.mark.parametrize("case", ["default", "momentum", "certified",
                                  "resumed", "planned"])
def test_sync_round_key_folded_in_program_is_bit_identical(case,
                                                           tmp_path):
    """A sync session's rounds (the key folded inside the round program
    where no plan covers t) equal the eager loop ``state <-
    run_round(state, fold_in(key, t), t)`` bit for bit; resumed mid-run
    (t starts at 2), and with a plan installed (the eager path)."""
    spec = qspec(**BIT_SPECS.get(case, {}))
    sub = api.make_substrate(spec)
    plan = (jax.random.split(jax.random.PRNGKey(3), 4)
            if case == "planned" else None)
    sess = api.FederationSession.create(spec, jax.random.PRNGKey(7),
                                        substrate=sub, round_keys=plan)
    state = sess.state
    for t in range(4):
        key = (plan[t] if plan is not None
               else jax.random.fold_in(sess.key, t))
        state, _ = sub.run_round(state, key, t)
    if case == "resumed":
        sess.run(2)
        path = str(tmp_path / "ckpt")
        sess.save(path)
        sess = api.FederationSession.resume(path, substrate=sub)
        assert sess.round == 2
        sess.run(2)
    else:
        sess.run(4)
    assert sess.round == 4
    assert_states_equal(sess.state, state)


# ------------------------------------------------------- async scheduling

def test_async_deterministic_and_distinct_from_sync():
    spec = qspec(schedule="async", async_commit=1, staleness_decay=0.5)
    runs = []
    for _ in range(2):
        sess = api.FederationSession.create(spec, jax.random.PRNGKey(2))
        sess.run(4, callbacks=[api.EvalEvery(2)])
        runs.append(sess)
    assert runs[0].history == runs[1].history  # fixed latency seed
    assert_states_equal(runs[0].state, runs[1].state)
    sync = api.FederationSession.create(
        dataclasses.replace(spec, schedule="sync"), jax.random.PRNGKey(2))
    sync.run(4, callbacks=[api.EvalEvery(2)])
    assert sync.history != runs[0].history  # stale commits change math
    m = runs[0].scheduler
    assert m.dispatched >= 1 and m.clock > 0.0


@pytest.mark.parametrize("make_spec", [qspec, cspec],
                         ids=["quantum", "classical"])
def test_async_kill_and_resume_mid_buffer_bit_exact(make_spec, tmp_path):
    spec = make_spec(schedule="async", async_commit=1,
                     staleness_decay=0.5, latency_seed=9)
    straight = api.FederationSession.create(spec, jax.random.PRNGKey(3))
    straight.run(3, callbacks=[api.EvalEvery(1)])

    killed = api.FederationSession.create(spec, jax.random.PRNGKey(3))
    killed.run(1, callbacks=[api.EvalEvery(1)])
    # K=1 < N_p=2 guarantees in-flight uploads at the kill point
    assert killed.scheduler.entries, "buffer must be non-empty"
    path = str(tmp_path / "async.npz")
    killed.save(path)
    del killed

    resumed = api.FederationSession.resume(path)
    assert isinstance(resumed.scheduler, api.AsyncScheduler)
    assert resumed.scheduler.entries  # buffer travelled
    resumed.run(2, callbacks=[api.EvalEvery(1)])
    assert resumed.history == straight.history
    assert_states_equal(resumed.state, straight.state)
    # the simulated clock and dispatch counter travelled too
    assert resumed.scheduler.clock == straight.scheduler.clock
    assert resumed.scheduler.dispatched == straight.scheduler.dispatched


def test_overlapped_kill_and_resume_bit_exact(tmp_path):
    spec = qspec(schedule="overlapped")
    straight = api.FederationSession.create(spec, jax.random.PRNGKey(6))
    straight.run(4, callbacks=[api.EvalEvery(2)])

    killed = api.FederationSession.create(spec, jax.random.PRNGKey(6))
    killed.run(2, callbacks=[api.EvalEvery(2)])
    assert killed.scheduler.pending is not None
    path = str(tmp_path / "overlap.npz")
    killed.save(path)
    del killed

    resumed = api.FederationSession.resume(path)
    assert resumed.scheduler.pending is not None  # pending round rode
    resumed.run(2, callbacks=[api.EvalEvery(2)])
    assert resumed.history == straight.history
    assert_states_equal(resumed.state, straight.state)


def test_flush_drains_pipeline_and_buffer():
    # overlapped: flush commits the pending round without advancing it
    sess = api.FederationSession.create(qspec(schedule="overlapped"),
                                        jax.random.PRNGKey(8))
    sess.run(2)
    before = [np.asarray(p).copy() for p in sess.state]
    sess.flush()
    assert sess.scheduler.pending is None
    assert sess.round == 2
    assert any(not np.array_equal(np.asarray(a), b)
               for a, b in zip(sess.state, before))
    sess.flush()  # idempotent once drained
    # async: flush commits every buffered upload
    a = api.FederationSession.create(
        qspec(schedule="async", async_commit=1), jax.random.PRNGKey(8))
    a.run(1)
    assert a.scheduler.entries
    a.flush()
    assert not a.scheduler.entries
    # sync: nothing in flight
    s = api.FederationSession.create(qspec(), jax.random.PRNGKey(8))
    s.run(1)
    s.flush()


# --------------------------------------------- server-side outer optimizer

def test_server_opt_beta_zero_is_plain_server_classical():
    base = cspec()
    mom = cspec(server_opt="momentum", server_momentum=0.0)
    a = api.FederationSession.create(base, jax.random.PRNGKey(0))
    b = api.FederationSession.create(mom, jax.random.PRNGKey(0))
    a.run(2)
    b.run(2)
    for k in a.state["params"]:
        np.testing.assert_array_equal(np.asarray(a.state["params"][k]),
                                      np.asarray(b.state["params"][k]))
    assert "sopt" in b.state and "sopt" not in a.state


def test_server_opt_momentum_changes_trajectory_and_checkpoints(tmp_path):
    spec = qspec(aggregation="average", server_opt="nesterov",
                 server_momentum=0.8)
    plain = api.FederationSession.create(
        dataclasses.replace(spec, server_opt="none"),
        jax.random.PRNGKey(1))
    sess = api.FederationSession.create(spec, jax.random.PRNGKey(1))
    plain.run(3)
    sess.run(3)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(plain.state, sess.state["params"]))
    assert sess.state["smom"] is not None
    # momentum state rides in state_flat -> kill-and-resume is bit-exact
    straight = api.FederationSession.create(spec, jax.random.PRNGKey(1))
    straight.run(3)
    killed = api.FederationSession.create(spec, jax.random.PRNGKey(1))
    killed.run(2)
    path = str(tmp_path / "sopt.npz")
    killed.save(path)
    resumed = api.FederationSession.resume(path)
    resumed.run(1)
    assert_states_equal(resumed.state, straight.state)


# ------------------------------------------------- quantization channel

def test_quantization_channel_unbiased_and_tightens_with_bits():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    errs = []
    for bits in (4, 8, 12):
        q = fchannel.make_channel("quantize", bits=bits)(key, [x])[0]
        errs.append(float(jnp.max(jnp.abs(q - x))))
        # values land on the grid: steps of max|x| / (2^{bits-1}-1)
        step = float(jnp.max(jnp.abs(x))) / (2 ** (bits - 1) - 1)
        assert errs[-1] <= step + 1e-6
    assert errs[0] > errs[1] > errs[2]
    # stochastic rounding is unbiased: mean over keys converges to x
    # (4-bit grid step ~max|x|/7, so SE over 400 draws is ~1e-2 — the
    # tolerance is a ~5-sigma band, not a grid-resolution claim)
    ch = fchannel.make_channel("quantize", bits=4)
    qs = jnp.stack([ch(jax.random.PRNGKey(i), [x])[0]
                    for i in range(400)])
    np.testing.assert_allclose(np.asarray(qs.mean(0)), np.asarray(x),
                               atol=6e-2)


def test_quantization_channel_complex_and_spec_driven():
    # complex uploads quantize per real/imag part and keep their dtype
    k = jax.random.PRNGKey(3)
    z = (jax.random.normal(jax.random.PRNGKey(4), (4, 4))
         + 1j * jax.random.normal(jax.random.PRNGKey(5), (4, 4)))
    q = fchannel.make_channel("quantize", bits=10)(k, [z])[0]
    assert q.dtype == z.dtype
    assert float(jnp.max(jnp.abs(q - z))) < 0.02 * float(
        jnp.max(jnp.abs(z)))
    # a quantized federation trains end-to-end from the spec field
    sess = api.FederationSession.create(qspec(quantize_bits=8),
                                        jax.random.PRNGKey(0))
    sess.run(2, callbacks=[api.EvalEvery(2)])
    assert np.isfinite(sess.history["test_fidelity"]).all()
