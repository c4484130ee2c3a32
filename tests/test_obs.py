"""``repro.obs``: spans and counters inside the federation program."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.fed.api.session import EvalEvery, FederationSession
from repro.core.fed.api.spec import FedSpec
from repro.core.fed.serve import CheckpointStore, FederationServer

SPEC = FedSpec.quantum((2, 3, 2), num_nodes=4, nodes_per_round=2,
                       n_per_node=4, interval_length=2, n_test=4)


@pytest.fixture
def recording():
    """Spans recorded for the test's length; cleared either side."""
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


def _tree(snap):
    """{span id: record} and {parent id: [child names]}."""
    by_id = {s["id"]: s for s in snap["spans"]}
    kids = {}
    for s in snap["spans"]:
        kids.setdefault(s["parent"], []).append(s["name"])
    return by_id, kids


def _children(snap, name):
    """The child names of every span called ``name``."""
    _, kids = _tree(snap)
    return [sorted(kids.get(s["id"], [])) for s in snap["spans"]
            if s["name"] == name]


def test_spans_nest_and_nothing_is_recorded_while_off():
    obs.reset()
    assert not obs.ON
    # off: one shared object, nothing kept
    assert obs.span("qfed.a") is obs.span("qfed.b")
    with obs.span("qfed.a"):
        obs.interval("qfed.q", 0.0, 1.0, "r")
    assert obs.snapshot()["spans"] == []
    obs.enable()
    try:
        with obs.span("qfed.outer"):
            with obs.span("qfed.inner"):
                time.sleep(0.002)
            with obs.span("qfed.inner"):
                pass
        obs.interval("qfed.q", 1.0, 1.5, "r7")
    finally:
        obs.disable()
    snap = obs.snapshot()
    obs.reset()
    outer = [s for s in snap["spans"] if s["name"] == "qfed.outer"]
    inner = [s for s in snap["spans"] if s["name"] == "qfed.inner"]
    assert len(outer) == 1 and len(inner) == 2
    assert outer[0]["parent"] is None
    assert all(s["parent"] == outer[0]["id"] for s in inner)
    assert all(outer[0]["t0"] <= s["t0"] <= s["t1"] <= outer[0]["t1"]
               for s in inner)
    q = [s for s in snap["spans"] if s["name"] == "qfed.q"]
    assert q == [{"name": "qfed.q", "id": q[0]["id"], "parent": None,
                  "t0": 1.0, "t1": 1.5, "rid": "r7"}]
    # aggregates are keyed by path
    agg = snap["aggregates"]
    assert sorted(agg) == ["qfed.outer", "qfed.outer/qfed.inner", "qfed.q"]
    inner_agg = agg["qfed.outer/qfed.inner"]
    assert inner_agg["n"] == 2
    assert inner_agg["longest_s"] >= 0.002
    assert agg["qfed.outer"]["self_s"] == pytest.approx(
        agg["qfed.outer"]["seconds"] - inner_agg["seconds"])
    assert agg["qfed.q"] == {"n": 1, "seconds": 0.5, "self_s": 0.5,
                             "longest_s": 0.5}


def test_counters_are_always_on():
    obs.reset()
    assert not obs.ON
    obs.count("qfed.x")
    obs.count("qfed.x", 4)
    obs.count("qfed.y")
    assert obs.snapshot()["counters"] == {"qfed.x": 5, "qfed.y": 1}
    obs.reset()
    assert obs.snapshot()["counters"] == {}


def test_a_compile_is_counted_under_its_span(recording):
    # a shape no other test compiles
    x = jnp.ones((3, 7, 11), jnp.float32)
    with obs.span("qfed.step"):
        jax.jit(lambda a: a * 3.0 + 1.0)(x).block_until_ready()
    jax.jit(lambda a: a - 2.0)(x).block_until_ready()
    counters = obs.snapshot()["counters"]
    assert counters.get("qfed.compile[qfed.step]", 0) >= 1
    assert counters.get("qfed.compile[]", 0) >= 1


def test_session_step_and_eval_spans(recording):
    sess = FederationSession.create(SPEC, jax.random.PRNGKey(0))
    sess.run(2, callbacks=[EvalEvery(2, final=False)])
    snap = obs.snapshot()
    agg = snap["aggregates"]
    assert agg["qfed.step"]["n"] == 2
    # the session, and the substrate it builds
    assert agg["qfed.create"]["n"] == 1
    assert agg["qfed.create/qfed.create"]["n"] == 1
    # no plan: the round program folds the key in, no eager round_key
    assert "qfed.step/qfed.round_key" not in agg
    assert snap["counters"]["qfed.round_key.in_program"] == 2
    assert agg["qfed.eval/qfed.fetch"]["n"] == 2
    assert _children(snap, "qfed.step") == [["qfed.round"]] * 2
    # EvalEvery records at round 0 and round 2
    assert _children(snap, "qfed.eval") == [["qfed.fetch"]] * 2
    by_id, _ = _tree(snap)
    for s in snap["spans"]:
        if s["name"] == "qfed.create" and s["parent"] is not None:
            assert by_id[s["parent"]]["name"] == "qfed.create"


def test_sync_rounds_fold_the_key_in_program(recording):
    sess = FederationSession.create(SPEC, jax.random.PRNGKey(0))
    sess.step()  # compiles
    before = obs.snapshot()["counters"]["qfed.round_key.in_program"]
    sess.run(3)
    snap = obs.snapshot()
    assert snap["counters"]["qfed.round_key.in_program"] - before == 3
    assert snap["aggregates"]["qfed.step"]["n"] == 4
    assert not [s for s in snap["spans"] if s["name"] == "qfed.round_key"]


@pytest.mark.parametrize("how", ["planned", "async"])
def test_eager_round_keys_keep_their_span(recording, how):
    if how == "planned":
        sess = FederationSession.create(SPEC, jax.random.PRNGKey(0),
                                        rounds=3)
    else:
        sess = FederationSession.create(
            dataclasses.replace(SPEC, schedule="async", async_commit=2),
            jax.random.PRNGKey(0))
    sess.run(3)
    snap = obs.snapshot()
    assert "qfed.round_key.in_program" not in snap["counters"]
    keys = [s for s in snap["spans"] if s["name"] == "qfed.round_key"]
    assert len(keys) == 3


def test_server_tick_spans_and_queue_intervals(recording, tmp_path):
    server = FederationServer(slots=2, store_dir=str(tmp_path))
    sids = [server.submit(SPEC, key=jax.random.PRNGKey(i), rounds=2)
            for i in range(3)]
    server.tick()
    snap = obs.snapshot()
    assert snap["aggregates"]["qfed.submit"]["n"] == 3
    assert _children(snap, "qfed.submit") == [["qfed.create"]] * 3
    assert _children(snap, "qfed.tick") == [sorted(
        ["qfed.admit", "qfed.seat", "qfed.dispatch", "qfed.retire"])]
    assert _children(snap, "qfed.dispatch") == [["qfed.fetch"]]
    queue = [s for s in snap["spans"] if s["name"] == "qfed.queue"]
    # two slots: the first two jobs are seated, the third waits
    assert sorted(s["rid"] for s in queue) == sids[:2]
    assert all(s["t1"] >= s["t0"] for s in queue)
    server.drain()
    queue = [s for s in obs.snapshot()["spans"] if s["name"] == "qfed.queue"]
    assert sorted(s["rid"] for s in queue) == sids


def _served_params(tmp_path):
    server = FederationServer(slots=2, store_dir=str(tmp_path))
    for i in range(3):
        server.submit(SPEC, key=jax.random.PRNGKey(i), rounds=3, sid=f"t{i}")
    server.drain()
    solo = FederationSession.create(SPEC, jax.random.PRNGKey(5))
    solo.run(3, callbacks=[EvalEvery(1)])
    out = [np.asarray(p) for i in range(3) for p in
           server.session(f"t{i}").substrate.state_parts(
               server.session(f"t{i}").state)[0]]
    return out + [np.asarray(p) for p in solo.state], solo.history


def test_states_are_bit_identical_with_spans_on_and_off(tmp_path):
    obs.reset()
    off, hist_off = _served_params(tmp_path / "off")
    obs.enable()
    try:
        on, hist_on = _served_params(tmp_path / "on")
    finally:
        obs.disable()
    assert obs.snapshot()["aggregates"]["qfed.tick"]["n"] > 0
    obs.reset()
    assert hist_on == hist_off
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


def test_capped_store_counts_parks_and_revives(tmp_path):
    obs.reset()
    store = CheckpointStore(str(tmp_path), capacity=1)
    store.add("a", FederationSession.create(SPEC, jax.random.PRNGKey(0)))
    store.add("b", FederationSession.create(SPEC, jax.random.PRNGKey(1)))
    store.get("a")            # revives "a", parks "b"
    store.park("a")
    counters = obs.snapshot()["counters"]
    obs.reset()
    assert counters == {"qfed.park": 3, "qfed.revive": 1}
