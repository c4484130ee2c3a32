"""Traffic kind ``to_target``: back-to-back training episodes, each to a
target test fidelity.

The mix file gives the federation, the target and the round cap. The
data set is made once from the seed; every episode starts from fresh
weights drawn from the seed and runs ``FederationSession.run(cap)`` with
an evaluation after every round (``EvalEvery(1)``) and ``EarlyStop`` at
the target. An episode's time runs from creating its session to the
stop; one that reaches the cap is failed. The episode still running
when the window closes is left out.

Set-up runs episode 0, the window runs episodes 1, 2, ...: the same
compiled round and evaluation. Episode 0's first three rounds, with the
evaluations the program recorded, are what is compared.
"""
from __future__ import annotations

import gc
import time

import jax

from chipbench import compare, inputs, work
from chipbench.kinds import train as train_kind
from chipbench.harness import Window


def _callbacks(api, tr, spans=None, deadline=None, snaps=None):
    class TimedEval(api.EvalEvery):
        def _record(self, session):
            if spans is None:
                return super()._record(session)
            with spans("eval"):
                return super()._record(session)

    class Clock(api.Callback):
        """Ends the span of the step that just ran, keeps the first
        states for the check and stops at the window's close."""

        step = None

        def on_round_end(self, session, metrics):
            if self.step is not None:
                spans.close(self.step)
                self.step = None
            if snaps is not None and len(snaps) < train_kind.CHECK_ROUNDS:
                snaps.append(train_kind.host(session.state))
            if deadline is not None and time.perf_counter() >= deadline:
                session.request_stop()

    class Mark(api.Callback):
        """Opens the span of the next step, after every other callback."""

        def on_run_begin(self, session):
            if spans is not None:
                clock.step = spans.open("step")

        def on_round_end(self, session, metrics):
            self.on_run_begin(session)

        def on_run_end(self, session):
            if clock.step is not None:
                spans.close(clock.step)
                clock.step = None

    clock = Clock()
    return [clock, TimedEval(1, final=False),
            api.EarlyStop("test_fidelity", target=float(tr["target"])),
            Mark()]


def _episode(st, e, spans=None, deadline=None, snaps=None):
    """Run episode ``e``; returns (seconds, rounds, reached, session)."""
    from repro.core.fed import api
    params = inputs.make_params(jax.random.fold_in(st.key, 1000 + e),
                                widths=tuple(st.ctx.config["widths"]))
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    sess = api.FederationSession.create(
        st.spec, jax.random.fold_in(st.key, 2000 + e), substrate=st.sub,
        params=list(params))
    sess.run(int(st.ctx.traffic["cap"]),
             callbacks=_callbacks(api, st.ctx.traffic, spans, deadline, snaps))
    seconds = time.perf_counter() - t0
    reached = sess.last_eval.get("test_fidelity", 0.0) >= st.ctx.traffic["target"]
    return seconds, sess.round, reached, sess, params


def setup(ctx):
    st = train_kind.State()
    st.ctx = ctx
    st.spec, st.sub, st.fed, st.key = train_kind.build(ctx)
    snaps = []
    _, _, _, sess, params = _episode(st, 0, snaps=snaps)
    st.snaps = [train_kind.host(params)] + snaps
    st.session_key = jax.random.fold_in(st.key, 2000)
    st.history = {k: list(v) for k, v in sess.history.items()}
    st.params0 = params
    return st


def window(st, seconds, spans):
    t0 = time.perf_counter()
    deadline = t0 + seconds
    done, failed, rounds = [], 0, 0
    e = 1
    with spans("window"):
        while time.perf_counter() < deadline:
            sec, n, reached, _, _ = _episode(st, e, spans, deadline)
            e += 1
            rounds += n
            if reached:
                done.append(sec)
            elif n >= st.ctx.traffic["cap"]:
                failed += 1
    tr = st.ctx.traffic
    return Window(seconds=time.perf_counter() - t0, rounds=rounds,
                  attempted=len(done) + failed, failed=failed,
                  samples={"episode_s": done},
                  ops_per_round=work.round_ops(
                      st.ctx.config["widths"], tr["nodes_per_round"],
                      tr["interval_length"], tr["n_per_node"],
                      tr["aggregation"]))


def _reference(st, precision=None, **fault):
    cfg, tr = st.ctx.config, st.ctx.traffic
    mod = st.ctx.bench.module("reference", cfg["reference"])
    ref = train_kind.reference(st, precision)
    states = mod.train(ref, st.params0, st.fed.phi_in, st.fed.phi_out,
                       st.session_key, train_kind.CHECK_ROUNDS,
                       nodes_per_round=tr["nodes_per_round"],
                       interval_length=tr["interval_length"],
                       aggregation=tr["aggregation"], **fault)
    return ref, [train_kind.host(s) for s in states]


def _fidelities(ref, st, states):
    d = st.fed.phi_in.shape[-1]
    train = [float(ref.fidelity(s, st.fed.phi_in.reshape(-1, d),
                                st.fed.phi_out.reshape(-1, d)))
             for s in states]
    test = [float(ref.fidelity(s, st.fed.test_in, st.fed.test_out))
            for s in states]
    return train, test


def check(st, precision=None, stale_eval=False, **fault):
    st.sub = None
    gc.collect()
    n = train_kind.CHECK_ROUNDS
    if precision is None and not fault and not stale_eval:
        ref, states = _reference(st)
        prog = st.snaps
        hist = st.history
        p_train = hist["train_fidelity"][1:n + 1]
        p_test = hist["test_fidelity"][:n + 1]
    else:
        # the control or a fault stands in the program's place
        _, prog = _reference(st, precision, **fault)
        ref, states = _reference(st)
        p_train, p_test = _fidelities(ref, st, prog)
        p_train = p_train[1:]
        if stale_eval:
            p_test = p_test[:1] + p_test[:-1]
    r_train, r_test = _fidelities(ref, st, states)
    out = compare.training(prog, states, p_train, r_train[1:])
    out["eval_gap"] = max(abs(a - b) for a, b in zip(p_test, r_test))
    return out
