"""``Substrate`` — what a federation round runs ON.

The session's scheduler drives federations through this protocol and
never branches on which physics it is driving:

* ``init_state(key, params=None)`` — build the opaque federation state
  (global model + whatever per-node / server-optimizer state the
  substrate keeps).
* ``run_round(state, key, round)`` — one QuanFedPS synchronization
  iteration (Alg. 1 + Alg. 2): the CANONICAL composition of the four
  round phases (``repro.core.fed.api.phases``), fused where the
  substrate can; returns ``(new_state, metrics)``.
* ``run_round_from(state, base_key, t)`` — ``run_round`` under the
  session's round key ``fold_in(base_key, t)``. The protocol's default
  folds eagerly; ``QuantumSubstrate`` folds inside its round program,
  so a sync round is one device launch.
* the four phases themselves — ``select`` / ``local_update`` /
  ``transmit`` / ``aggregate`` (+ ``split_round_key`` and
  ``upload_restore``) — for schedulers that interleave phases of
  different rounds (async buffering, overlapped dispatch).
* ``evaluate(state)`` — metric dict of PYTHON floats, pulled from the
  device in ONE ``jax.device_get`` (a single host sync per record, not
  one blocking ``float(...)`` per metric).
* ``state_flat(state)`` / ``state_restore(flat)`` — the checkpoint
  boundary: a nested tree of arrays for ``repro.checkpoint`` and its
  exact inverse.

``QuantumSubstrate`` wraps the ``core/quantum/federated`` phase kernels;
``ClassicalSubstrate`` wraps ``core/fed/fed_step``'s (``node_uploads`` /
``aggregate_deltas``) plus the per-node inner-optimizer state. Both can
be built from a ``FedSpec`` alone via ``make_substrate`` when the spec
carries a data recipe — which is what lets ``FederationSession.resume``
reconstruct a federation from nothing but a checkpoint file.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.fed import channel as fchannel
from repro.core.fed import participation, server_opt as fserver_opt
from repro.core.fed import fed_step
from repro.core.fed.api.phases import Cohort, compose_round
from repro.core.fed.api.spec import FedSpec


class Substrate(Protocol):
    """The physics-agnostic face a federation session drives."""

    spec: FedSpec

    def init_state(self, key: jax.Array, params: Any = None) -> Any:
        ...

    def run_round(self, state: Any, key: jax.Array, round: int
                  ) -> Tuple[Any, Dict[str, Any]]:
        ...

    def run_round_from(self, state: Any, base_key: jax.Array, t
                       ) -> Tuple[Any, Dict[str, Any]]:
        with obs.span("qfed.round_key"):
            key = jax.random.fold_in(base_key, t)
        return self.run_round(state, key, int(t))

    def evaluate(self, state: Any) -> Dict[str, float]:
        ...

    def state_flat(self, state: Any) -> Dict[str, Any]:
        ...

    def state_restore(self, flat: Dict[str, Any]) -> Any:
        ...


def _device_get_floats(tree) -> Dict[str, float]:
    """One host transfer for a (possibly nested) dict of scalars."""
    with obs.span("qfed.fetch"):
        host = jax.device_get(tree)
    flat = {}

    def walk(prefix, t):
        if isinstance(t, dict):
            for k, v in t.items():
                walk(f"{prefix}{k}" if not prefix else f"{prefix}_{k}", v)
        else:
            flat[prefix] = float(t)

    walk("", host)
    return flat


class QuantumSubstrate(Substrate):
    """QuanFedPS on the dissipative-QNN simulator (Alg. 1/2 proper).

    State is the QNN params: a list of per-layer stacked complex
    unitaries — or, with ``spec.server_opt != "none"``, the dict
    ``{"params": [...], "smom": [...] | None}`` carrying the server
    momentum on the aggregated generators (None until the first
    aggregation). With the certified approximate-rank engine on
    (``spec.rank_tol`` / ``rank_cap`` / ``ensemble_dtype``) the state is
    always the dict form and additionally carries ``"err_bound"`` — the
    RUNNING sum of per-round error certificates; each round's increment
    is reported in the round metrics and ``evaluate`` surfaces the
    accumulated total alongside fidelity. Pass ``dataset``/``test``
    explicitly, or leave them None to rebuild both from the spec's data
    recipe (deterministic in ``spec.data_seed``).
    """

    def __init__(self, spec: FedSpec, dataset=None,
                 test: Optional[Tuple[jax.Array, jax.Array]] = None):
        with obs.span("qfed.create"):
            self._build(spec, dataset, test)

    def _build(self, spec, dataset, test):
        from repro.core.quantum import data as qdata

        if spec.substrate != "quantum":
            raise ValueError(f"QuantumSubstrate needs a quantum spec, got "
                             f"{spec.substrate!r}")
        from repro.core.quantum import linalg as ql

        self.spec = spec
        self.cfg = spec.to_quantum_config()
        self._certified = ql.resolve_approx(
            spec.rank_tol, spec.rank_cap, spec.ensemble_dtype) is not None
        if (dataset is None) != (test is None):
            # regenerating one half from the recipe would pair it with a
            # DIFFERENT hidden target unitary than the provided half
            raise ValueError("pass both dataset= and test= (same target "
                             "unitary) or neither")
        if dataset is None:
            if spec.n_per_node is None and spec.node_sizes is None:
                raise ValueError(
                    "spec carries no data recipe (n_per_node / node_sizes)"
                    " — pass dataset= and test= explicitly")
            _, dataset, test = qdata.make_federated_dataset(
                jax.random.PRNGKey(spec.data_seed), int(spec.widths[0]),
                num_nodes=spec.num_nodes, n_per_node=spec.n_per_node or 0,
                noise_ratio=spec.data_noise, iid=spec.data_iid,
                n_test=spec.n_test, node_sizes=spec.node_sizes)
        self.dataset = dataset
        self.test = test
        # defense="screen" scores each upload on a server probe batch —
        # the held-out test pairs double as the probe
        self._probe = ((test[0], test[1]) if spec.defense == "screen"
                       else None)
        # flattened train view for evaluation (padded slots masked out)
        self._train_in = dataset.phi_in.reshape(-1, dataset.phi_in.shape[-1])
        self._train_out = dataset.phi_out.reshape(
            -1, dataset.phi_out.shape[-1])
        vmask = dataset.valid_mask()
        self._train_w = None if vmask is None else vmask.reshape(-1)

    def _params_of(self, state):
        return state["params"] if isinstance(state, dict) else state

    def _smom_of(self, state):
        return state.get("smom") if isinstance(state, dict) else None

    def _err_of(self, state):
        if isinstance(state, dict) and "err_bound" in state:
            return state["err_bound"]
        return jnp.zeros(())

    def _pack(self, params, smom, err_bound=None):
        if self.spec.server_opt == "none" and not self._certified:
            return params  # legacy state shape, bit-compatible ckpts
        state = {"params": params, "smom": smom}
        if self._certified:
            state["err_bound"] = (jnp.zeros(()) if err_bound is None
                                  else err_bound)
        return state

    def init_state(self, key: jax.Array, params: Any = None):
        from repro.core.quantum import qnn
        if params is None:
            params = qnn.init_params(key, self.spec.widths)
        return self._pack(params, None)

    def run_round(self, state, key, round):
        from repro.core.quantum import federated as fed
        del round  # the quantum round is pure in (state, key)
        return self._round_out(state, *fed.server_round_certified(
            self._params_of(state), self.dataset, key, self.cfg,
            smom=self._smom_of(state), server_opt=self.spec.server_opt,
            server_beta=self.spec.server_momentum, probe=self._probe))

    def run_round_from(self, state, base_key, t):
        # one launch: the round program folds t into the key itself
        from repro.core.quantum import federated as fed
        obs.count("qfed.round_key.in_program")
        return self._round_out(state, *fed.server_round_from(
            self._params_of(state), self.dataset, base_key, t, self.cfg,
            smom=self._smom_of(state), server_opt=self.spec.server_opt,
            server_beta=self.spec.server_momentum, probe=self._probe))

    def _round_out(self, state, params, smom, bound):
        if not self._certified:
            return self._pack(params, smom), {}
        err = self._err_of(state) + bound
        return (self._pack(params, smom, err),
                {"err_bound_round": bound, "err_bound_total": err})

    # -- the four phases (see repro.core.fed.api.phases) ----------------
    def split_round_key(self, key: jax.Array):
        # the fused round's exact splits: selection / node / channel
        k_sel, k_loc, k_tx = jax.random.split(jnp.asarray(key), 3)
        return k_sel, k_loc, k_tx

    def select(self, key: jax.Array, round: int) -> Cohort:
        from repro.core.quantum import federated as fed
        sel, pmask, weights = fed.select_phase(self.dataset, key, self.cfg)
        return Cohort(sel=sel, mask=pmask, weights=weights, round=round)

    def local_update(self, state, cohort: Cohort, key: jax.Array):
        from repro.core.quantum import federated as fed
        if not self._certified:
            ks_all = fed.local_phase(self._params_of(state), self.dataset,
                                     cohort.sel, key, self.cfg)
            return state, ks_all, {}
        # certified engine: the cohort's per-node certificates combine
        # with its selection weights at dispatch time (the uploads are
        # approximate the moment they are born, whatever round they
        # later commit in) and accumulate into the state's running total
        ks_all, bounds = fed.local_phase(self._params_of(state),
                                         self.dataset, cohort.sel, key,
                                         self.cfg, with_bound=True)
        bound = jnp.sum(cohort.weights.astype(bounds.dtype) * bounds)
        err = self._err_of(state) + bound
        state = self._pack(self._params_of(state), self._smom_of(state),
                           err)
        return state, ks_all, {"err_bound_round": bound,
                               "err_bound_total": err}

    def transmit(self, uploads, key: jax.Array):
        from repro.core.quantum import federated as fed
        return fed.transmit_phase(uploads, key, self.cfg)

    def aggregate(self, state, received, weights: jax.Array):
        from repro.core.quantum import federated as fed
        params, smom = fed.aggregate_phase(
            self._params_of(state), received, weights, self.cfg,
            smom=self._smom_of(state), server_opt=self.spec.server_opt,
            server_beta=self.spec.server_momentum, probe=self._probe)
        return self._pack(params, smom, self._err_of(state))

    def upload_restore(self, flat: Dict[str, Any]):
        n_layers = len(self.spec.widths) - 1
        return [jnp.asarray(flat[str(i)]) for i in range(n_layers)]

    # -- evaluation / checkpoint ----------------------------------------
    def evaluate(self, state) -> Dict[str, float]:
        from repro.core.quantum import federated as fed
        params = self._params_of(state)
        tr = fed.evaluate(params, self._train_in, self._train_out,
                          self.spec.widths, impl=self.spec.impl,
                          weights=self._train_w)
        te = fed.evaluate(params, self.test[0], self.test[1],
                          self.spec.widths, impl=self.spec.impl)
        tree = {"train": tr, "test": te}
        if self._certified:
            # the certificate travels with fidelity: accumulated bound
            # on how far the approximate engine may have drifted
            tree["err_bound"] = self._err_of(state)
        return _device_get_floats(tree)

    def state_flat(self, state) -> Dict[str, Any]:
        flat = {"params": list(self._params_of(state))}
        smom = self._smom_of(state)
        if smom is not None:
            flat["smom"] = list(smom)
        if self._certified:
            flat["err_bound"] = self._err_of(state)
        return flat

    def state_restore(self, flat: Dict[str, Any]):
        n_layers = len(self.spec.widths) - 1
        params = [jnp.asarray(flat[f"params/{i}"])
                  for i in range(n_layers)]
        smom = None
        if any(k.startswith("smom/") for k in flat):
            smom = [jnp.asarray(flat[f"smom/{i}"])
                    for i in range(n_layers)]
        err = (jnp.asarray(flat["err_bound"]) if "err_bound" in flat
               else None)
        return self._pack(params, smom, err)

    # -- serving (stacked multi-tenant rounds) --------------------------
    def smom_zeros(self, params):
        """The zero server-momentum state, materialized: per layer
        (I_l,) + params[l].shape — the shape of the averaged generators
        K̄_k the momentum recursion runs on. Numerically identical to
        the lazy ``None`` round-0 state (``generator_step`` treats None
        as zeros), but structure-stable, so stacked session states keep
        one pytree shape whatever round each tenant is at."""
        il = self.spec.interval_length
        return [jnp.zeros((il,) + p.shape, p.dtype) for p in params]

    def state_parts(self, state):
        """``(params, smom, err_bound)`` in a STRUCTURE-STABLE form —
        what the serving layer stacks over the session axis: ``smom``
        is materialized via ``smom_zeros`` when the spec carries a
        server optimizer but no momentum has accumulated yet, ``smom``
        / ``err_bound`` are None exactly when the spec never tracks
        them. ``pack_state`` is the inverse."""
        params = self._params_of(state)
        smom = self._smom_of(state)
        if self.spec.server_opt != "none" and smom is None:
            smom = self.smom_zeros(params)
        err = self._err_of(state) if self._certified else None
        return params, smom, err

    def pack_state(self, params, smom=None, err_bound=None):
        """Rebuild a session state from ``state_parts`` output (public
        face of ``_pack`` for the serving layer)."""
        return self._pack(params, smom, err_bound)


class ClassicalSubstrate(Substrate):
    """QuanFedPS's classical limit: I_l local optimizer steps per node +
    weighted delta aggregation (``fed_train_round``) on a pytree model.

    State is ``{"params": model params, "opt": per-node inner optimizer
    states}`` (+ ``"sopt"``, the server-side outer-optimizer state, when
    ``spec.server_opt != "none"``). Data is a deterministic per-round
    pool stream rebuilt from the spec (seeded ``token_batches``), so a
    resumed substrate fast-forwards the stream to the checkpointed round
    and continues bit-exactly.
    """

    def __init__(self, spec: FedSpec, model=None, opt=None):
        from repro.configs import get_config
        from repro.models import Model
        from repro.optim import AdamW

        if spec.substrate != "classical":
            raise ValueError(f"ClassicalSubstrate needs a classical spec, "
                             f"got {spec.substrate!r}")
        if spec.arch is None:
            raise ValueError("classical spec needs arch")
        self.spec = spec
        reduced_kw = {} if spec.n_layers is None else {
            "n_layers": spec.n_layers}
        self.cfg = get_config(spec.arch).reduced(**reduced_kw)
        self.model = model if model is not None else Model(self.cfg)
        self.opt = opt if opt is not None else AdamW(weight_decay=0.0)
        self.loss_fn = lambda p, b: self.model.loss_fn(p, b)
        from repro.core.fed.config import FederatedConfig
        # fed_train_round sees only the SELECTED nodes: its num_nodes is
        # the per-round count N_p, not the global N
        self.fed_cfg = FederatedConfig(
            num_nodes=spec.nodes_per_round,
            nodes_per_round=spec.nodes_per_round,
            interval_length=spec.interval_length,
            aggregation=spec.aggregation,
            participation=spec.participation,
            dropout_rate=spec.dropout_rate, outer_lr=spec.outer_lr,
            delta_dtype=spec.delta_dtype)
        self._delta_dt = fed_step.resolve_delta_dtype(self.fed_cfg)
        self._server_sgd = fserver_opt.make_sgd(spec.server_opt,
                                                spec.server_momentum)
        # classical wire: quantization if the spec asks (Hermitian noise
        # is quantum-only — real deltas have no GUE perturbation)
        self._channel = fchannel.resolve_channel(0.0, spec.quantize_bits)
        self._pool_seqs = spec.node_pool_seqs or spec.node_batch * 2
        # unequal nodes: the pool must cover the requested true volumes
        self._pool_total = (sum(spec.node_sizes) if spec.node_sizes
                            else spec.num_nodes * self._pool_seqs)
        self._data = None
        self._pos = 0
        from repro.data import token_batches
        self.eval_batch = next(token_batches(
            self.cfg, spec.eval_batch, spec.seq_len,
            seed=spec.data_seed + 99))

    def init_state(self, key: jax.Array, params: Any = None):
        if params is None:
            params = self.model.init(key)
        opt_nodes = jax.vmap(lambda _: self.opt.init(params))(
            jnp.arange(self.spec.nodes_per_round))
        state = {"params": params, "opt": opt_nodes}
        if self._server_sgd is not None:
            state["sopt"] = self._server_sgd.init(params)
        return state

    def _pool(self, round: int):
        """The round's global data pool — the ``round``-th item of the
        seeded stream, regardless of what was consumed before (rewinds
        by recreating the iterator, fast-forwards by draining it)."""
        from repro.data import token_batches
        if self._data is None or self._pos > round:
            self._data = token_batches(
                self.cfg, self._pool_total, self.spec.seq_len,
                seed=self.spec.data_seed)
            self._pos = 0
        while self._pos < round:
            next(self._data)
            self._pos += 1
        pool = next(self._data)
        self._pos += 1
        return pool

    def run_round(self, state, key, round):
        # the canonical phase composition — executed eagerly, so it is
        # bit-exact with the pre-phase fed_train_round monolith
        return compose_round(self, state, key, round)

    # -- the four phases (see repro.core.fed.api.phases) ----------------
    def split_round_key(self, key: jax.Array):
        # legacy parity: node selection consumed the WHOLE round key;
        # the local phase draws no randomness, and the channel key is a
        # fresh derivation (only consumed by the new quantize channel)
        key = jnp.asarray(key)
        return key, jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)

    def select(self, key: jax.Array, round: int) -> Cohort:
        from repro.data import partition_iid, partition_non_iid
        from repro.data.partition import node_token_counts

        spec = self.spec
        pool = self._pool(round)
        nodes = (partition_iid(pool, spec.num_nodes,
                               seed=spec.data_seed + round,
                               node_seqs=spec.node_sizes)
                 if spec.data_iid else
                 partition_non_iid(pool, spec.num_nodes,
                                   node_seqs=spec.node_sizes))
        # TRUE per-node token counts from the partition (Alg. 2's N_n) —
        # weighted participation / data-volume rounds see real volumes
        node_tokens = node_token_counts(nodes)
        nodes.pop("n_seqs", None)  # counts consumed; not a batch entry
        sel, pmask = participation.sample_nodes(
            key, spec.num_nodes, spec.nodes_per_round,
            schedule=spec.participation, node_sizes=node_tokens,
            dropout_rate=spec.dropout_rate,
            method=spec.participation_method)
        sel_batches = jax.tree.map(lambda x: x[sel], nodes)

        def to_steps(x):  # split each node's pool into I_l local steps
            per = x.shape[1] // spec.interval_length
            return x[:, : per * spec.interval_length].reshape(
                (x.shape[0], spec.interval_length, per) + x.shape[2:])

        node_batches = jax.tree.map(to_steps, sel_batches)
        weights = participation.round_weights(
            self.fed_cfg.participation,
            node_tokens[sel].astype(jnp.float32),
            pmask.astype(jnp.float32))
        return Cohort(sel=sel, mask=pmask, weights=weights, round=round,
                      data=node_batches)

    def local_update(self, state, cohort: Cohort, key: jax.Array):
        del key  # the classical local pass draws no randomness
        deltas, opt_nodes, metrics = fed_step.node_uploads(
            self.loss_fn, self.opt, state["params"], state["opt"],
            cohort.data, self.spec.lr, self._delta_dt)
        state = dict(state, opt=opt_nodes)
        return state, deltas, dict(jax.tree.map(jnp.mean, metrics))

    def transmit(self, uploads, key: jax.Array):
        return self._channel(key, uploads)

    def aggregate(self, state, received, weights: jax.Array):
        params, sopt = fed_step.aggregate_deltas(
            state["params"], received, weights, self.spec.outer_lr,
            server_sgd=self._server_sgd, server_state=state.get("sopt"),
            defense=self.spec.defense, trim_frac=self.spec.trim_frac,
            clip_norm=self.spec.clip_norm)
        state = dict(state, params=params)
        if self._server_sgd is not None:
            state["sopt"] = sopt
        return state

    def upload_restore(self, flat: Dict[str, Any]):
        # a delta tree mirrors the params tree: a FLAT dict of arrays
        return {k: jnp.asarray(v) for k, v in flat.items()}

    def evaluate(self, state) -> Dict[str, float]:
        loss = self.loss_fn(state["params"], self.eval_batch)[0]
        return _device_get_floats({"eval_loss": loss})

    def state_flat(self, state) -> Dict[str, Any]:
        flat = {"params": state["params"], "opt": state["opt"]}
        if "sopt" in state:
            flat["sopt"] = state["sopt"]
        return flat

    def state_restore(self, flat: Dict[str, Any]):
        from repro import checkpoint as ckpt
        # model params are a FLAT dict with '/' in its keys — stripping
        # the "params/" prefix recovers exactly the original keys
        params = {k[len("params/"):]: jnp.asarray(v)
                  for k, v in flat.items() if k.startswith("params/")}
        opt_tpl = jax.eval_shape(
            lambda _: jax.vmap(lambda __: self.opt.init(params))(
                jnp.arange(self.spec.nodes_per_round)), 0)
        opt_nodes = ckpt.unflatten_like(
            opt_tpl, {k[len("opt/"):]: v for k, v in flat.items()
                      if k.startswith("opt/")})
        state = {"params": params, "opt": opt_nodes}
        if self._server_sgd is not None:
            state["sopt"] = ckpt.unflatten_like(
                self._server_sgd.init(params),
                {k[len("sopt/"):]: v for k, v in flat.items()
                 if k.startswith("sopt/")})
        return state


def make_substrate(spec: FedSpec) -> Substrate:
    """Build the substrate a spec names, data included (the spec must
    carry a data recipe — see ``FedSpec``)."""
    if spec.substrate == "quantum":
        return QuantumSubstrate(spec)
    return ClassicalSubstrate(spec)
