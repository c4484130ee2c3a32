#!/usr/bin/env python3
"""Bring-up smoke test: the federated QNN main path on a TPU.

    python3 chip_smoke.py              # one chip: paper, wide, serve
    python3 chip_smoke.py --chips 4    # four chips: the pod path only

One chip runs three phases through the entry points a user calls:

* ``paper`` — the quickstart federation (widths (2,3,2), 100 nodes, 10
  per round, I_l=2, Eq. 6 product) driven through ``FederationSession``;
  the test fidelity must rise.
* ``wide`` — widths (4,5,4), the widest exact width, for a few rounds
  with ``engine="local"`` on ``impl="xla"`` and on ``impl="pallas"``,
  and with ``engine="dense"`` as the oracle. The three must agree, the
  updated layer unitaries must stay unitary, the Pallas eval must match
  the XLA one, and the compiled Pallas round and eval must hold
  ``tpu_custom_call`` (compiled kernels, not interpreted ones).
* ``serve`` — a ``FederationServer`` with two stacked tenants of the
  quickstart federation; each served tenant must match the same session
  stepped alone.

``--chips 4`` runs only ``pods``, at the quickstart federation with 8
nodes per round: the round with ``fanout="shard_map"`` over a
four-device ``('pod',)`` mesh and the ``two_level`` topology with four
pods, each against the one-device ``vmap`` round, and checks that each
pod's slice of the node axis lands on its own device.

Data and weights are made from fixed seeds. The lines before the last
are bring-up readings, not benchmark numbers. The last line is one JSON
object naming the device. Without a TPU the script exits non-zero
before it runs anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.fed import api  # noqa: E402
from repro.core.fed.cohort import hierarchy as fhierarchy  # noqa: E402
from repro.core.fed.cohort import topology as ftopology  # noqa: E402
from repro.core.fed.serve import FederationServer, StackedGroup  # noqa: E402
from repro.core.quantum import data as qdata  # noqa: E402
from repro.core.quantum import federated as fed  # noqa: E402
from repro.core.quantum import linalg as ql  # noqa: E402
from repro.core.quantum import qnn  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.sharding import rules  # noqa: E402

# Tolerances. The chip computes in complex64 (no entry point enables
# x64). Measured on the CPU in complex64 at these configurations, the
# engines agree to ~1e-6 after three rounds and the layer unitaries
# stay unitary to ~1e-6; the bounds leave an order of magnitude of
# headroom for the TPU's different summation order.
PARITY_TOL = 1e-4      # max |param difference| between engines / paths
UNITARY_TOL = 1e-4     # max |U U† - I| of every updated layer unitary
FIDELITY_RISE = 0.5    # paper phase: test fidelity gain over the run

# The quickstart federation (examples/quickstart.py).
PAPER = dict(widths=(2, 3, 2), num_nodes=100, nodes_per_round=10,
             interval_length=2, eta=1.0, eps=0.1, aggregation="product",
             n_per_node=4, n_test=32, data_seed=42)
# The widest width the exact engines run (BENCH_engine.json).
WIDE = dict(widths=(4, 5, 4), num_nodes=20, nodes_per_round=4,
            interval_length=2, eta=1.0, eps=0.1, aggregation="product",
            n_per_node=4, n_test=32, data_seed=3)
ENGINES = (("local", "xla"), ("local", "pallas"), ("dense", "xla"))
# engine pairs compared in the wide phase ("dense-xla" is the oracle)
PAIRS = (("local-xla", "dense-xla"), ("local-pallas", "dense-xla"),
         ("local-pallas", "local-xla"))

# XLA compile time, persistent-cache reads included (tracing is left
# out: nested jits report nested trace spans)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _check(ok, message: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {message}")


def _max_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a, b))


def _unitarity_error(params) -> float:
    """max |U U† - I| over every layer unitary."""
    return max(float(ql.unitarity_error(u)) for u in params)


def _params(session):
    return session.substrate.state_parts(session.state)[0]


def phase_paper(rounds: int = 20, **overrides) -> dict:
    """The quickstart federation: test fidelity must rise."""
    spec = api.FedSpec.quantum(**{**PAPER, **overrides})
    sess = api.FederationSession.create(spec, jax.random.PRNGKey(7))
    before = sess.evaluate()["test_fidelity"]
    sess.run(rounds)
    after = sess.evaluate()["test_fidelity"]
    _check(after >= before + FIDELITY_RISE,
           f"test fidelity {before:.4f} -> {after:.4f} after {rounds} "
           f"rounds, need a rise of {FIDELITY_RISE}")
    unitarity = _unitarity_error(_params(sess))
    _check(unitarity <= UNITARY_TOL,
           f"paper phase layers left the unitary group: max |UU†-I| "
           f"{unitarity:.3g} > {UNITARY_TOL}")
    return {"rounds": rounds, "test_fidelity_before": before,
            "test_fidelity_after": after, "max_unitarity_error": unitarity}


def phase_wide(rounds: int = 3, expect_kernels: bool = True,
               **overrides) -> dict:
    """local-xla, local-pallas and dense agree; layers stay unitary;
    the Pallas eval matches the XLA one; the Pallas round and eval are
    compiled kernels."""
    base = api.FedSpec.quantum(**{**WIDE, **overrides})
    params, fidelity, unitarity = {}, {}, {}
    for engine, impl in ENGINES:
        name = f"{engine}-{impl}"
        spec = dataclasses.replace(base, engine=engine, impl=impl)
        sess = api.FederationSession.create(spec, jax.random.PRNGKey(11))
        sess.run(rounds)
        params[name] = _params(sess)
        test_in, test_out = sess.substrate.test
        fidelity[name] = float(fed.evaluate(params[name], test_in, test_out,
                                            spec.widths)["fidelity"])
        unitarity[name] = _unitarity_error(params[name])
        for u in params[name]:
            _check(bool(ql.is_unitary(u, atol=UNITARY_TOL)),
                   f"{name}: layer not unitary to {UNITARY_TOL} "
                   f"(max |UU†-I| {unitarity[name]:.3g})")
        if impl != "pallas":
            continue
        xla_eval = fed.evaluate(params[name], test_in, test_out, spec.widths)
        kernel_eval = fed.evaluate(params[name], test_in, test_out,
                                   spec.widths, impl="pallas")
        eval_dev = max(abs(float(kernel_eval[k]) - float(xla_eval[k]))
                       for k in ("fidelity", "mse"))
        _check(eval_dev <= PARITY_TOL,
               f"pallas vs xla eval: max |dmetric| {eval_dev:.3g}")
        if expect_kernels:
            sub = sess.substrate
            lowered = {
                "round": fed.lower_server_round(
                    params[name], sub.dataset, sess.key, sub.cfg),
                "eval": fed.evaluate.lower(
                    params[name], test_in, test_out, spec.widths,
                    impl="pallas")}
            for what, low in lowered.items():
                _check("tpu_custom_call" in low.compile().as_text(),
                       f"compiled pallas {what} holds no tpu_custom_call")
    deviation = {f"{a} vs {b}": _max_diff(params[a], params[b])
                 for a, b in PAIRS}
    for name, dev in deviation.items():
        _check(dev <= PARITY_TOL,
               f"{name}: max |dparam| {dev:.3g} > {PARITY_TOL}")
    return {"widths": list(base.widths), "rounds": rounds,
            "max_dparam": deviation, "max_unitarity_error": unitarity,
            "pallas_vs_xla_eval_max_dmetric": eval_dev,
            "test_fidelity": fidelity}


def phase_serve(rounds: int = 3, **overrides) -> dict:
    """Two tenants of the paper federation on one stacked grid == each
    stepped alone."""
    base = api.FedSpec.quantum(**{**PAPER, **overrides})
    tenants = [(dataclasses.replace(base, eta=eta),
                jax.random.PRNGKey(21 + i))
               for i, eta in enumerate((1.0, 0.7))]
    with tempfile.TemporaryDirectory() as store_dir:
        server = FederationServer(slots=2, store_dir=store_dir)
        sids = [server.submit(spec, key=key, rounds=rounds)
                for spec, key in tenants]
        ticks = server.drain()
        (group,) = server.groups.values()
        _check(isinstance(group, StackedGroup),
               f"tenants ran in a {type(group).__name__}, not stacked")
        deviation = []
        for sid, (spec, key) in zip(sids, tenants):
            served = server.session(sid)
            solo = api.FederationSession.create(spec, key)
            for _ in range(rounds):
                solo.step()
            _check(served.round == solo.round == rounds,
                   f"served round {served.round}, solo {solo.round}")
            deviation.append(_max_diff(_params(served), _params(solo)))
    _check(max(deviation) <= PARITY_TOL,
           f"served vs solo: max |dparam| {max(deviation):.3g} > "
           f"{PARITY_TOL}")
    return {"tenants": len(tenants), "rounds": rounds, "ticks": ticks,
            "max_dparam_served_vs_solo": deviation}


def _check_split(x, n: int, what: str) -> None:
    """Shard i of ``x``'s leading axis is block i, each on its own
    device."""
    shards = sorted(x.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    per = x.shape[0] // n
    _check(len(shards) == n and len({s.device for s in shards}) == n,
           f"{what}: {len(shards)} shards on "
           f"{len({s.device for s in shards})} devices, want {n}")
    for i, s in enumerate(shards):
        _check((s.index[0].start or 0, s.index[0].stop) ==
               (i * per, (i + 1) * per),
               f"{what}: shard {i} holds rows {s.index[0]}")


def phase_pods(n_pods: int = 4, **overrides) -> dict:
    """The pod path over an n_pods-device ('pod',) mesh vs one device."""
    # the paper's network: four chips are charged four times, and these
    # widths keep the phase's compiles short
    conf = {**PAPER, "nodes_per_round": 2 * n_pods, **overrides}
    widths = tuple(conf["widths"])
    _, ds, _ = qdata.make_federated_dataset(
        jax.random.PRNGKey(conf["data_seed"]), widths[0],
        num_nodes=conf["num_nodes"], n_per_node=conf["n_per_node"],
        n_test=4)
    params = qnn.init_params(jax.random.PRNGKey(1), widths)
    key = jax.random.PRNGKey(2)
    flat = fed.QuantumFedConfig(
        widths=widths, num_nodes=conf["num_nodes"],
        nodes_per_round=conf["nodes_per_round"],
        interval_length=conf["interval_length"], eta=conf["eta"],
        eps=conf["eps"], aggregation=conf["aggregation"])
    mesh = make_mesh((n_pods,), ("pod",))
    deviation = {}
    for name, cfg in (("shard_map", flat),
                      ("two_level", flat._replace(topology="two_level",
                                                  pods=n_pods))):
        one_device = fed.server_round(params, ds, key, cfg)
        with jax.set_mesh(mesh):
            pods = fed.server_round(params, ds, key,
                                    cfg._replace(fanout="shard_map"))
        deviation[name] = _max_diff(pods, one_device)
        _check(deviation[name] <= PARITY_TOL,
               f"{name} vs one-device vmap: max |dparam| "
               f"{deviation[name]:.3g} > {PARITY_TOL}")
    with jax.set_mesh(mesh):
        cfg = flat._replace(fanout="shard_map")
        sel, _, _ = fed.select_phase(ds, key, cfg)
        ks_all = fed.local_phase(params, ds, sel, key, cfg)
        for li, ks in enumerate(ks_all):
            _check_split(ks, n_pods, f"layer {li} node uploads")
        topo = ftopology.resolve_topology("two_level", n_pods, "block")
        pod_partials = jax.jit(functools.partial(
            fhierarchy.pod_products, topo=topo,
            mesh=rules.current_mesh()))(ks_all[0])
        _check_split(pod_partials, n_pods, "two-level pod partials")
    return {"pods": n_pods, "widths": list(widths),
            "nodes_per_round": conf["nodes_per_round"],
            "max_dparam_vs_one_device": deviation}


class _CompileClock:
    """Seconds of XLA compilation and persistent-cache hits."""

    def __init__(self):
        self.secs = 0.0
        self.cache_hits = 0

    def on_duration(self, event, duration_secs, **_):
        if event == _COMPILE_EVENT:
            self.secs += duration_secs

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pod path over four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports platform "
              f"{devices[0].platform!r}; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devices)}", file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    kind = devices[0].device_kind
    print(f"bring-up reading, not a benchmark: device {kind} x"
          f"{len(devices)}, compile cache {cache_dir}", flush=True)

    phases = ([("pods", phase_pods)] if args.chips == 4 else
              [("paper", phase_paper), ("wide", phase_wide),
               ("serve", phase_serve)])
    for name, phase in phases:
        secs0, hits0 = clock.secs, clock.cache_hits
        t0 = time.perf_counter()
        readings = phase()
        wall = time.perf_counter() - t0
        peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
        print(f"bring-up reading, not a benchmark: phase {name} passed: "
              f"wall {wall:.3f} s, compile {clock.secs - secs0:.3f} s, "
              f"persistent-cache hits {clock.cache_hits - hits0}, "
              f"peak_bytes_in_use {peak}, {json.dumps(readings)}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
