"""Traffic kind ``train``: one federation trained round after round.

The mix file gives the federation (nodes, nodes per round, I_l, pairs
per node, test pairs, aggregation, topology, fan-out). Set-up makes the data and the initial weights from the
seed, builds one ``FederationSession`` and drives it through its first
three rounds with the window's own call, ``session.run`` with the
window's callback; the window then runs that same session back to back
with no evaluation, keeping at most ``inflight`` rounds queued on the
device. A round counts when its state is ready on the device.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import numpy as np

from chipbench import compare, inputs, work
from chipbench.harness import Window

CHECK_ROUNDS = 3


class Pacer:
    """The window's callback: spans each ``session.step`` from the end
    of one callback to the start of the next, keeps at most ``depth``
    rounds in flight by blocking on the state ``depth`` rounds back, and
    stops the run when the window's time is up."""

    def __init__(self, depth: int, seconds: float = float("inf"),
                 spans=None):
        self.depth = depth
        self.seconds = seconds
        self.spans = spans
        self.states = collections.deque()
        self.t0 = None
        self.step = None

    def _open(self):
        if self.spans is not None:
            self.step = self.spans.open("step")

    def on_run_begin(self, session):
        self.t0 = time.perf_counter()
        self._open()

    def on_round_end(self, session, metrics):
        if self.step is not None:
            self.spans.close(self.step)
            self.step = None
        self.states.append(session.state)
        if len(self.states) > self.depth:
            old = self.states.popleft()
            if self.spans is not None:
                with self.spans("sync"):
                    jax.block_until_ready(old)
            else:
                jax.block_until_ready(old)
        if time.perf_counter() - self.t0 >= self.seconds:
            session.request_stop()
        self._open()

    def on_run_end(self, session):
        if self.step is not None:
            self.spans.close(self.step)
            self.step = None
        self.states.clear()


def host(params):
    return [np.asarray(p) for p in params]


def federation(ctx):
    """The federation's inputs made from the seed, and the session key."""
    cfg, tr = ctx.config, ctx.traffic
    key = inputs.seed_key(ctx.seed)
    fed = inputs.make_federation(
        jax.random.fold_in(key, 0), widths=tuple(cfg["widths"]),
        num_nodes=tr["num_nodes"], n_per_node=tr["n_per_node"],
        n_test=tr["n_test"])
    return fed, jax.random.fold_in(key, 1)


def build(ctx):
    """The federation's inputs and its substrate."""
    from repro.core.fed import api
    from repro.core.quantum.data import QuantumDataset

    cfg, tr = ctx.config, ctx.traffic
    fed, key = federation(ctx)
    spec = api.FedSpec.quantum(
        widths=tuple(cfg["widths"]), num_nodes=tr["num_nodes"],
        nodes_per_round=tr["nodes_per_round"],
        interval_length=tr["interval_length"], eta=cfg["eta"],
        eps=cfg["eps"], aggregation=tr["aggregation"],
        engine=cfg["engine"], impl=cfg["impl"],
        fanout=tr.get("fanout", "auto"), topology=tr.get("topology", "flat"))
    sub = api.QuantumSubstrate(
        spec, dataset=QuantumDataset(fed.phi_in, fed.phi_out),
        test=(fed.test_in, fed.test_out))
    return spec, sub, fed, key


class State:
    pass


def setup(ctx):
    from repro.core.fed import api

    st = State()
    st.ctx = ctx
    st.spec, sub, st.fed, st.key = build(ctx)
    st.session = api.FederationSession.create(
        st.spec, st.key, substrate=sub, params=list(st.fed.params))
    st.depth = int(ctx.traffic.get("inflight", 8))
    st.snaps = [host(st.fed.params)]
    for _ in range(CHECK_ROUNDS):
        st.session.run(1, callbacks=[Pacer(st.depth)])
        st.snaps.append(host(st.session.state))
    return st


def window(st, seconds, spans):
    sess = st.session
    start = sess.round
    pacer = Pacer(st.depth, seconds, spans)
    t0 = time.perf_counter()
    with spans("window"):
        sess.run(10 ** 9, callbacks=[pacer])
        jax.block_until_ready(sess.state)
    elapsed = time.perf_counter() - t0
    rounds = sess.round - start
    tr = st.ctx.traffic
    return Window(seconds=elapsed, rounds=rounds, attempted=rounds, failed=0,
                  ops_per_round=work.round_ops(
                      st.ctx.config["widths"], tr["nodes_per_round"],
                      tr["interval_length"], tr["n_per_node"],
                      tr["aggregation"]))


def reference(st, precision=None, eta=None):
    """The configuration's plain reference (one per eta and precision,
    so each compiles once)."""
    cfg = st.ctx.config
    eta = cfg["eta"] if eta is None else eta
    refs = st.__dict__.setdefault("refs", {})
    key = (eta, None if precision is None else tuple(sorted(precision.items())))
    if key not in refs:
        mod = st.ctx.bench.module("reference", cfg["reference"])
        refs[key] = mod.Reference(cfg["widths"], eta, cfg["eps"],
                                  **({} if precision is None else
                                     {"precision": precision}))
    return refs[key]


def reference_run(st, precision=None, **fault):
    """The reference's states over the check rounds."""
    cfg, tr = st.ctx.config, st.ctx.traffic
    mod = st.ctx.bench.module("reference", cfg["reference"])
    ref = reference(st, precision)
    states = mod.train(ref, st.fed.params, st.fed.phi_in, st.fed.phi_out,
                       st.key, CHECK_ROUNDS,
                       nodes_per_round=tr["nodes_per_round"],
                       interval_length=tr["interval_length"],
                       aggregation=tr["aggregation"], **fault)
    return ref, [host(s) for s in states]


def train_loss(ref, fed, params):
    d = fed.phi_in.shape[-1]
    return float(ref.fidelity(params, fed.phi_in.reshape(-1, d),
                              fed.phi_out.reshape(-1, d)))


def check(st, precision=None, **fault):
    st.session = None
    gc.collect()
    ref, states = reference_run(st, precision, **fault)
    if precision is not None or fault:
        # the control or a fault stands in the program's place; the
        # full-precision reference is what it is compared against
        prog = states
        ref, states = reference_run(st)
    else:
        prog = st.snaps
    p_loss = [train_loss(ref, st.fed, s) for s in prog[1:]]
    r_loss = [train_loss(ref, st.fed, s) for s in states[1:]]
    return compare.training(prog, states, p_loss, r_loss)
