"""Traffic kind ``serve_closed``: a ``FederationServer`` under a closed
loop of tenant clients.

The mix file gives the server (``slots``, ``rounds_per_tick``), the
number of clients, the federation every job runs, and the job mix. Each
client submits a job, waits for it to retire with its round budget
spent, and submits the next. Job ``j`` gets its own data and weights
made from the seed, a session key, a round budget, an aggregation and
an eta; the budgets, aggregations and etas come in blocks that hold a
fixed set of values in an order drawn from the seed (every block of
``budget_hi - budget_lo + 1`` jobs holds each budget once, every block
of ``agg_block`` jobs holds ``agg_average`` average jobs, every block of
``len(etas)`` jobs each eta once), so every seed asks for the same work.

Set-up submits one job per client and runs ``warmup_ticks`` ticks of
the loop, which compiles every program the window runs. The window
ticks the server until its time is up, then stops submitting and ticks
on until every job submitted in the window has retired. A job's latency
runs from the start of its submission (session creation included) to
the end of the tick that retired it, once its state is ready.
"""
from __future__ import annotations

import gc
import tempfile
import time

import jax
import numpy as np

from chipbench import compare, inputs, work
from chipbench.kinds import train as train_kind
from chipbench.harness import Window

SAMPLE = 16


class Jobs:
    """The job sequence of one seed."""

    def __init__(self, seed: int, tr: dict):
        self.seed = int(seed)
        self.tr = tr

    def _block(self, tag: int, block: int):
        return np.random.default_rng([self.seed, tag, block])

    def budget(self, j: int) -> int:
        lo, hi = self.tr["budget_lo"], self.tr["budget_hi"]
        n = hi - lo + 1
        return lo + int(self._block(1, j // n).permutation(n)[j % n])

    def aggregation(self, j: int) -> str:
        n = self.tr["agg_block"]
        avg = self._block(2, j // n).choice(n, self.tr["agg_average"],
                                            replace=False)
        return "average" if (j % n) in avg else "product"

    def eta(self, j: int) -> float:
        etas = self.tr["etas"]
        return float(etas[int(self._block(3, j // len(etas))
                              .permutation(len(etas))[j % len(etas)])])


class State:
    pass


def _spec(st, j):
    from repro.core.fed import api
    cfg, tr = st.ctx.config, st.ctx.traffic
    return api.FedSpec.quantum(
        widths=tuple(cfg["widths"]), num_nodes=tr["num_nodes"],
        nodes_per_round=tr["nodes_per_round"],
        interval_length=tr["interval_length"], eta=st.jobs.eta(j),
        eps=cfg["eps"], aggregation=st.jobs.aggregation(j),
        engine=cfg["engine"], impl=cfg["impl"])


def _job_inputs(st, j):
    tr = st.ctx.traffic
    return inputs.make_federation(
        jax.random.fold_in(st.key, 10_000 + j),
        widths=tuple(st.ctx.config["widths"]), num_nodes=tr["num_nodes"],
        n_per_node=tr["n_per_node"], n_test=tr["n_test"])


def _job_key(st, j):
    return jax.random.fold_in(st.key, 20_000 + j)


def _submit(st, spans):
    """The next job's client prepares its data, then submits."""
    from repro.core.fed import api
    from repro.core.quantum.data import QuantumDataset
    j = st.next_job
    st.next_job += 1
    spec = _spec(st, j)
    fed = _job_inputs(st, j)
    jax.block_until_ready(fed)
    t0 = time.perf_counter()
    with spans("submit"):
        sub = api.QuantumSubstrate(
            spec, dataset=QuantumDataset(fed.phi_in, fed.phi_out),
            test=(fed.test_in, fed.test_out))
        sess = api.FederationSession.create(spec, _job_key(st, j),
                                            substrate=sub,
                                            params=list(fed.params))
        sid = st.server.submit(session=sess, rounds=st.jobs.budget(j),
                               sid=f"j{j:07d}")
    st.inflight[sid] = (j, t0)
    return sid


def _tick(st, spans):
    """One tick; returns (tenant rounds stepped, retired (sid, job, t_submit))."""
    with spans("tick"):
        stats = st.server.tick()
    retired = [(sid, *st.inflight.pop(sid)) for sid in list(st.inflight)
               if sid in st.server.done]
    for sid, _, _ in retired:
        jax.block_until_ready(st.server.session(sid).state)
    return stats["stepped"], retired, stats


def setup(ctx):
    from repro.core.fed.serve import FederationServer
    st = State()
    st.ctx = ctx
    tr = ctx.traffic
    st.key = inputs.seed_key(ctx.seed)
    st.jobs = Jobs(ctx.seed, tr)
    st.store = tempfile.TemporaryDirectory(prefix="chipbench-serve-")
    ctx.stack.callback(st.store.cleanup)
    st.server = FederationServer(slots=tr["slots"],
                                 rounds_per_tick=tr["rounds_per_tick"],
                                 store_dir=st.store.name)
    st.inflight = {}
    st.next_job = 0
    st.window_jobs = []
    from chipbench.harness import Spans
    quiet = Spans()
    for _ in range(tr["clients"]):
        _submit(st, quiet)
    for _ in range(tr["warmup_ticks"]):
        _, retired, _ = _tick(st, quiet)
        for _ in retired:
            _submit(st, quiet)
    return st


def window(st, seconds, spans):
    latencies, rounds, failed = [], 0, 0
    mine = set()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with spans("window"):
        while True:
            n, retired, stats = _tick(st, spans)
            now = time.perf_counter()
            rounds += n
            failed += stats["quarantined"]
            for sid, j, t_sub in retired:
                if sid in mine:
                    latencies.append(now - t_sub)
                    st.window_jobs.append((sid, j))
                if now < deadline:
                    mine.add(_submit(st, spans))
            if now >= deadline:
                break
    elapsed = time.perf_counter() - t0
    # every job submitted in the window is waited for; its latency
    # counts the wait, its rounds after the close do not count
    while mine & set(st.inflight):
        _, retired, stats = _tick(st, spans)
        now = time.perf_counter()
        failed += stats["quarantined"]
        for sid, j, t_sub in retired:
            if sid in mine:
                latencies.append(now - t_sub)
                st.window_jobs.append((sid, j))
    return Window(seconds=elapsed, rounds=rounds,
                  attempted=len(mine), failed=failed,
                  samples={"job_s": latencies},
                  ops_per_round=_round_ops(st))


def _round_ops(st):
    """Operations of one tenant round, counted at the cheaper of the
    mix's aggregations so that a round is never counted too high."""
    cfg, tr = st.ctx.config, st.ctx.traffic
    return min(work.round_ops(cfg["widths"], tr["nodes_per_round"],
                              tr["interval_length"], tr["n_per_node"], a)
               for a in ("product", "average"))


def sample(st):
    """The compared jobs: the longest budget of each aggregation, then
    others drawn from the seed, ``SAMPLE`` in all."""
    jobs = sorted(st.window_jobs, key=lambda sj: sj[1])
    hi = st.ctx.traffic["budget_hi"]
    picked = []
    for agg in ("product", "average"):
        longest = [sj for sj in jobs if st.jobs.budget(sj[1]) == hi
                   and st.jobs.aggregation(sj[1]) == agg]
        picked += longest[:1]
    rest = [sj for sj in jobs if sj not in picked]
    rng = np.random.default_rng([st.ctx.seed, 4])
    n = max(0, min(SAMPLE - len(picked), len(rest)))
    picked += [rest[i] for i in sorted(rng.choice(len(rest), n, replace=False))]
    return picked


def _reference_job(st, j, precision=None, rounds=None, **fault):
    cfg, tr = st.ctx.config, st.ctx.traffic
    mod = st.ctx.bench.module("reference", cfg["reference"])
    ref = train_kind.reference(st, precision, eta=st.jobs.eta(j))
    fed = _job_inputs(st, j)
    n = st.jobs.budget(j) if rounds is None else rounds
    return train_kind.host(mod.train(
        ref, fed.params, fed.phi_in, fed.phi_out, _job_key(st, j), n,
        nodes_per_round=tr["nodes_per_round"],
        interval_length=tr["interval_length"],
        aggregation=st.jobs.aggregation(j), **fault)[-1])


def _served(st):
    """The compared jobs' final states as the server left them (read
    once; the server is then dropped)."""
    if getattr(st, "served", None) is None:
        st.served = []
        for sid, j in sample(st):
            sess = st.server.session(sid)
            st.served.append((j, sess.round, train_kind.host(
                sess.substrate.state_parts(sess.state)[0])))
        st.server = None
        gc.collect()
    return st.served


def check(st, precision=None, unchanged=False, short=False, **fault):
    served = _served(st)
    # no job to compare reads as a gap without end
    gap, budget_gap = (0.0, 0) if served else (float("inf"), 0)
    for j, rounds, prog in served:
        if precision is not None or fault or unchanged or short:
            # the control or a fault stands in the program's place
            n = st.jobs.budget(j) - (1 if short else 0)
            prog = (train_kind.host(_job_inputs(st, j).params) if unchanged
                    else _reference_job(st, j, precision, rounds=n, **fault))
            rounds = st.jobs.budget(j)
        ref = _reference_job(st, j)
        gap = max(gap, compare.state_gap(prog, ref))
        budget_gap = max(budget_gap, abs(rounds - st.jobs.budget(j)))
    return {"job_gap": gap, "budget_gap": float(budget_gap)}
