"""QuantumFed: QuanFedNode (Alg. 1) + QuanFedPS (Alg. 2).

The round is strategy-driven through the shared federation core
(``repro.core.fed``): aggregation modes come from the strategy registry
(``"product"`` Eq. 6, ``"average"`` Eq. 8, ``"served"`` = average over a
compressed wire), node selection from the participation schedules
(``"uniform"`` / ``"weighted"`` / ``"dropout"``), and upload noise from
the ChannelModel registry. Lemma 1 guarantees product and average agree
to O(eps^2); ``tests/test_quantumfed.py`` checks this, and that
interval_length=1 + full participation reproduces centralized training
exactly (§III-C).

Unequal node sizes: datasets may carry true per-node counts N_n
(``QuantumDataset.n_per``, padded batches + validity masks). The masks
flow through the node pass (minibatch selection and the Prop.-1 1/N
normalization), and Alg. 2's data-volume weights N_n/N_t use the real
counts.

Fan-out: the per-node QuanFedNode pass runs either as a single-device
``vmap`` or — when a mesh carrying the 'fed_node' → 'pod' rule axis is
active — under ``shard_map`` over the 'pod' axis, so each pod trains its
slice of the sampled nodes locally and the weighted aggregation is the
round's one cross-pod reduction (mirroring ``core/fed/fed_step.py``).
``QuantumFedConfig.fanout`` selects: "auto" (shard when >1 pod is
present), "vmap", or "shard_map".

Engine dispatch: ``QuantumFedConfig.engine`` selects the QNN simulation
path (``"local"`` low-rank vector ensembles on BOTH Prop.-1 chains,
default; ``"local_opb"`` the previous operator-space-B local engine,
kept as benchmark baseline; ``"dense"`` seed full-space reference) and
``QuantumFedConfig.impl`` the backend for the dense inner products
(``"xla"`` default; ``"pallas"`` for the TPU kernels — including the
fused ensemble-commutator-trace kernel — interpret mode on CPU). Both
update-unitary chains are rolled into ``jax.lax.scan`` (constant-size
jit graph in N_p and I_l), and all N_p x I_l x m_l update unitaries of
a layer are formed by a single batched ``expm_herm``. In the fused
round the node pass exports its per-K eigh factors and — when the
transmit phase is an exact identity (product combine, full-precision
wire, no channel noise/quantization) — ``aggregate_product`` reuses
them at the upload scale (e^{i eps (wK)} = V e^{i eps w lam} V†), so
each K is factored once per round instead of twice.

Phased round protocol: the round is composed of four phases —
``select_phase`` (participation sampling + Alg. 2 weights),
``local_phase`` (the QuanFedNode fan-out), ``transmit_phase`` (channel
model + wire cast) and ``aggregate_phase`` (strategy combine, optional
server-side generator momentum). ``server_round`` remains the canonical
composition, fused under ONE jit so sync training keeps its single
compiled round; schedulers that interleave rounds (async buffering,
overlapped dispatch) call the per-phase entry points, each jitted on
its own.

Multi-tenant serving: ``server_round_stacked`` vmaps the SAME round
body over a leading session axis, so a ``FederationServer``
(``repro.core.fed.serve``) drives every tenant of a group — same
structural config, own data/keys/hyperparameters — as one compiled
stacked round instead of S dispatches.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.fed import channel as fchannel
from repro.core.fed import participation, server_opt as fserver_opt
from repro.core.fed import strategies
from repro.core.fed.cohort import hierarchy as fhierarchy
from repro.core.fed.cohort import topology as ftopology
from repro.core.quantum import linalg as ql
from repro.core.quantum import qnn
from repro.core.quantum.data import QuantumDataset
from repro.sharding import rules


class QuantumFedConfig(NamedTuple):
    widths: Tuple[int, ...]
    num_nodes: int = 100          # N
    nodes_per_round: int = 10     # N_p
    interval_length: int = 1      # I_l
    eta: float = 1.0
    eps: float = 0.1
    minibatch: Optional[int] = None   # None => GD; int => SGD mini-batch
    aggregation: str = "product"      # strategy registry (fed.strategies)
    # beyond-paper: relative Hermitian noise on uploaded update matrices
    # (hardware/channel imperfection; uploads stay exactly unitary)
    upload_noise: float = 0.0
    engine: str = "local"             # "local" contractions | "dense" seed
    impl: str = "xla"                 # "xla" | "pallas" inner products
    participation: str = "uniform"    # schedule registry (fed.participation)
    participation_method: str = "auto"    # uniform-draw cost policy
    dropout_rate: float = 0.0         # straggler rate for "dropout"
    fanout: str = "auto"              # "auto" | "vmap" | "shard_map"
    # two-level aggregation tree (cohort registry): nodes -> pods -> root
    topology: str = "flat"            # "flat" | "two_level"
    pods: Optional[int] = None        # two_level: pod count
    pod_assignment: str = "block"     # "block" | "strided"
    quantize_bits: Optional[int] = None  # channel registry: "quantize"
    # certified approximate rank (engine="local" only): SVD-truncated
    # ensembles with a tracked error bound — see qnn.update_matrices.
    rank_tol: float = 0.0             # relative singular-value threshold
    rank_cap: Optional[int] = None    # absolute per-compression rank cap
    ensemble_dtype: Optional[str] = None  # None | "f32" | "bf16" storage
    # Byzantine-robust aggregation defense (strategies.DEFENSES):
    # "clip" | "trimmed_mean" | "median" harden the Eq. 8 mean;
    # "screen" quarantines Eq. 6 uploads by probe-batch fidelity.
    defense: Optional[str] = None
    trim_frac: float = 0.2            # trimmed_mean: trim fraction/side
    clip_norm: float = 1.0            # clip: per-matrix Frobenius bound
    screen_tol: float = 0.05          # screen: allowed fidelity drop


def _approx_on(cfg: QuantumFedConfig) -> bool:
    """True when cfg requests the certified approximate-rank engine
    (also validates the knobs — fails loudly before tracing)."""
    return ql.resolve_approx(cfg.rank_tol, cfg.rank_cap,
                             cfg.ensemble_dtype) is not None


def _topology_of(cfg: QuantumFedConfig):
    """The static aggregation-tree ``Topology`` a cfg names — None for
    flat. Validates fail-loud (pods dividing the cohort, block order for
    the product combine) before any tracing."""
    agg = strategies.get_aggregation(cfg.aggregation)
    ftopology.validate_topology(
        cfg.topology, cfg.pods, cfg.pod_assignment,
        nodes_per_round=cfg.nodes_per_round, combine=agg.combine)
    return ftopology.resolve_topology(cfg.topology, cfg.pods,
                                      cfg.pod_assignment)


def node_update(params: qnn.Params, phi_in: jax.Array, phi_out: jax.Array,
                key: jax.Array, eta, eps, cfg: QuantumFedConfig,
                mask: Optional[jax.Array] = None,
                return_factors: bool = False,
                with_bound: bool = False):
    """QuanFedNode: I_l temporary-update steps on one node's local data.

    mask: optional (n_per,) validity mask for padded unequal-size nodes —
    minibatch selection draws only valid pairs and the Prop.-1 average
    normalizes by the true count.

    Returns the per-step update matrices K_{n,k}, stacked per layer as
    (I_l, m_l, d, d). (Update *unitaries* are formed server-side from
    these; mathematically identical to Alg. 1's local storage and it lets
    both aggregation modes share one node pass.) With
    ``return_factors=True`` also returns the per-K eigh factors the
    temporary updates were formed from — (lam, v) per layer, stacked
    (I_l, m_l, d) / (I_l, m_l, d, d) — so a product-combine server can
    exponentiate the SAME K at the upload scale without a second eigh.
    ``with_bound=True`` appends the node's scalar approximation-error
    certificate (the per-step ``qnn.update_matrices`` bounds summed over
    the interval; 0.0 for exact configs).
    """
    n_per = phi_in.shape[0]

    def one_step(carry, key_k):
        p = carry
        if cfg.minibatch is not None and cfg.minibatch < n_per:
            if mask is None:
                idx = jax.random.choice(key_k, n_per, (cfg.minibatch,),
                                        replace=False)
                b_w = None
            else:
                p_sel = mask / jnp.maximum(jnp.sum(mask), 1e-12)
                idx = jax.random.choice(key_k, n_per, (cfg.minibatch,),
                                        replace=False, p=p_sel)
                b_w = mask[idx]
            b_in, b_out = phi_in[idx], phi_out[idx]
        else:
            b_in, b_out, b_w = phi_in, phi_out, mask
        out = qnn.update_matrices(p, b_in, b_out, cfg.widths, eta,
                                  engine=cfg.engine, impl=cfg.impl,
                                  weights=b_w, rank_tol=cfg.rank_tol,
                                  rank_cap=cfg.rank_cap,
                                  ensemble_dtype=cfg.ensemble_dtype,
                                  with_bound=with_bound)
        ks, bnd = out if with_bound else (out, None)
        factors = qnn.eigh_updates(ks)
        p = qnn.apply_updates_eigh(p, factors, eps, impl=cfg.impl)
        return p, ((ks, factors, bnd) if with_bound else (ks, factors))

    keys = jax.random.split(key, cfg.interval_length)
    _, out = jax.lax.scan(one_step, params, keys)
    if with_bound:
        ks_seq, factors_seq, bnds = out
        bound = jnp.sum(bnds)
        if return_factors:
            return ks_seq, factors_seq, bound
        return ks_seq, bound
    ks_seq, factors_seq = out
    if return_factors:
        return ks_seq, factors_seq
    return ks_seq  # list per layer: (I_l, m_l, d, d)


def _chain(us: jax.Array, upd: jax.Array, impl: str) -> jax.Array:
    """acc <- upd[T-1] @ ... @ upd[0] @ us via lax.scan (upd: (T, m, d, d))."""
    def body(acc, u):
        return qnn.bmm(u, acc, impl=impl), None

    acc, _ = jax.lax.scan(body, us, upd)
    return acc


def aggregate_product(params: qnn.Params, ks_all: List[jax.Array],
                      weights: jax.Array, eps, *, impl: str = "xla",
                      factors=None, topo=None, mesh=None) -> qnn.Params:
    """Eq. 6: U^{l,j} = prod_{k=I_l}^{1} prod_{n} e^{i eps w_n K_{n,k}},
    then U_{t+1} = U^{l,j} U_t^{l,j}.

    factors: optional per-layer (lam, v) eigh factors of the UNSCALED
    K's (exported by the node pass). When the wire between local and
    aggregate phases is an exact identity they are still valid and
    e^{i eps (w K)} = V e^{i eps w lam} V† skips the second eigh of
    every K in the round.

    topo: optional ``cohort.Topology`` — the two-level tree applies the
    SAME chain reassociated by pod (``hierarchy.tree_chain``), sharded
    over the mesh's 'pod' axis when ``mesh`` carries one.

    mesh (flat tree): the node-sharded uploads are gathered once and
    the chain runs replicated on every device, inside ``shard_map``.
    Left to the partitioner, the chain's input is re-gathered inside
    the scan, a schedule the TPU compiler fails on (internal error in
    its HLO scheduler at v5e 2x2).
    """
    if mesh is not None and topo is None:
        return jax.shard_map(
            lambda p, k, w, e, f: aggregate_product(p, k, w, e, impl=impl,
                                                    factors=f),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(
                params, ks_all, weights, eps, factors)
    new_params = []
    for li, (us, ks) in enumerate(zip(params, ks_all)):
        # ks: (N_p, I_l, m_l, d, d); one batched expm forms every scaled
        # update unitary of the round at once (weights cast here only).
        if factors is None:
            w = weights[:, None, None, None, None].astype(ks.dtype)
            upd = ql.expm_herm(ks * w, eps)
        else:
            lam, v = factors[li]  # (N_p, I_l, m_l, d), (N_p, I_l, m_l, d, d)
            wl = weights[:, None, None, None].astype(lam.dtype)
            upd = ql.expm_eigh(lam * wl, v, eps)
        if topo is not None:
            new_params.append(fhierarchy.tree_chain(us, upd, topo,
                                                    impl=impl, mesh=mesh))
            continue
        # Eq. 6 application order: interval step k outermost (k=1 applied
        # first), node n innermost — flatten to one scan sequence.
        seq = jnp.swapaxes(upd, 0, 1).reshape((-1,) + upd.shape[2:])
        new_params.append(_chain(us, seq, impl))
    return new_params


def aggregate_average(params: qnn.Params, ks_all: List[jax.Array],
                      weights: jax.Array, eps, *, impl: str = "xla",
                      topo=None, mesh=None) -> qnn.Params:
    """Eq. 8: K_k = sum_n w_n K_{n,k};  U = prod_{k=I_l}^{1} e^{i eps K_k}.

    topo: optional ``cohort.Topology`` — pods pre-sum their members'
    weighted generators and the cross-pod merge closes the sum (an exact
    reassociation; see ``hierarchy.tree_mean_generators``)."""
    new_params = []
    for us, ks in zip(params, ks_all):
        k_bar = _mean_generators(ks, weights, topo, mesh)
        upd = ql.expm_herm(k_bar, eps)  # (I_l, m_l, d, d)
        new_params.append(_chain(us, upd, impl))
    return new_params


def _mean_generators(ks: jax.Array, weights: jax.Array, topo, mesh
                     ) -> jax.Array:
    """One layer's Eq. 8 weighted generator mean — flat einsum
    (bit-compatible with the pre-tree aggregation) or the two-level
    pod-partial reassociation."""
    if topo is None:
        return jnp.einsum("n,nk...->k...", weights.astype(ks.dtype), ks)
    return fhierarchy.tree_mean_generators(ks, weights, topo, mesh=mesh)


def _node_batch(params: qnn.Params, node_in: jax.Array, node_out: jax.Array,
                node_keys: jax.Array, node_mask: Optional[jax.Array],
                eta, eps, cfg: QuantumFedConfig,
                with_factors: bool = False, with_bound: bool = False):
    """vmap the QuanFedNode pass over the leading node axis."""
    if node_mask is None:
        f = lambda ni, no, nk: node_update(params, ni, no, nk, eta, eps,
                                           cfg, return_factors=with_factors,
                                           with_bound=with_bound)
        return jax.vmap(f)(node_in, node_out, node_keys)
    f = lambda ni, no, nk, nm: node_update(params, ni, no, nk, eta, eps,
                                           cfg, nm,
                                           return_factors=with_factors,
                                           with_bound=with_bound)
    return jax.vmap(f)(node_in, node_out, node_keys, node_mask)


def _fan_out(params: qnn.Params, node_in: jax.Array, node_out: jax.Array,
             node_keys: jax.Array, node_mask: Optional[jax.Array],
             eta, eps, cfg: QuantumFedConfig, mesh,
             with_factors: bool = False, with_bound: bool = False):
    """Per-node fan-out: vmap, or shard_map over the 'fed_node' mesh axis
    (each pod runs its slice of the sampled nodes; the weighted
    aggregation that follows is the round's one cross-pod reduction)."""
    if cfg.fanout != "shard_map":
        return _node_batch(params, node_in, node_out, node_keys, node_mask,
                           eta, eps, cfg, with_factors, with_bound)
    axis = rules.fed_fanout_axis(mesh) if mesh is not None else None
    if axis is None:
        raise ValueError(
            "fanout='shard_map' needs a mesh carrying the 'fed_node' "
            "rule axis (e.g. 'pod'); use `with mesh:` or fanout='auto' "
            "for the vmap fallback")
    if cfg.nodes_per_round % mesh.shape[axis] != 0:
        raise ValueError(
            f"nodes_per_round={cfg.nodes_per_round} must be divisible by "
            f"mesh axis '{axis}' of size {mesh.shape[axis]}")
    rep, shard = P(), P(axis)
    if node_mask is None:
        body = lambda p, ni, no, nk, et, ep: _node_batch(
            p, ni, no, nk, None, et, ep, cfg, with_factors, with_bound)
        in_specs = (rep, shard, shard, shard, rep, rep)
        args = (params, node_in, node_out, node_keys, eta, eps)
    else:
        body = lambda p, ni, no, nk, nm, et, ep: _node_batch(
            p, ni, no, nk, nm, et, ep, cfg, with_factors, with_bound)
        in_specs = (rep, shard, shard, shard, shard, rep, rep)
        args = (params, node_in, node_out, node_keys, node_mask, eta, eps)
    fan = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=shard, check_vma=False)
    return fan(*args)


# --------------------------------------------------------------- phases
# The four phase bodies below are the round. `_server_round` composes
# them under ONE jit (bit-compatible with the pre-phase monolith); the
# `*_phase` wrappers further down jit each on its own for schedulers
# that interleave phases of different rounds.

def _select_impl(dataset: QuantumDataset, key: jax.Array,
                 cfg: QuantumFedConfig):
    """Alg. 2 node selection + the round's aggregation weights."""
    counts = dataset.node_counts()  # (N,) true data volumes N_n
    sel, pmask = participation.sample_nodes(
        key, cfg.num_nodes, cfg.nodes_per_round,
        schedule=cfg.participation, node_sizes=counts,
        dropout_rate=cfg.dropout_rate, method=cfg.participation_method)
    # Alg. 2 data-volume weights N_n/N_t from the TRUE per-node counts,
    # renormalized over the nodes the schedule kept (dropout zeroes a
    # straggler's weight; size-proportional sampling pairs with uniform
    # weights to stay unbiased). Kept real; the aggregators cast to the
    # complex state dtype only where the K's are scaled.
    weights = participation.round_weights(cfg.participation, counts[sel],
                                          pmask)
    return sel, pmask, weights


@ql.matmul_precision("ensemble")
def _local_impl(params: qnn.Params, dataset: QuantumDataset,
                sel: jax.Array, key: jax.Array, eta, eps,
                cfg: QuantumFedConfig, mesh, with_factors: bool = False,
                with_bound: bool = False):
    """QuanFedNode on every selected node (vmapped or pod-sharded)."""
    node_in = dataset.phi_in[sel]    # (N_p, n_max, d_in)
    node_out = dataset.phi_out[sel]  # (N_p, n_max, d_out)
    node_keys = jax.random.split(key, cfg.nodes_per_round)
    vmask = dataset.valid_mask()
    node_mask = None if vmask is None else vmask[sel]
    return _fan_out(params, node_in, node_out, node_keys, node_mask,
                    eta, eps, cfg, mesh, with_factors, with_bound)


def _factors_survive_wire(cfg: QuantumFedConfig) -> bool:
    """True when the node pass's eigh factors are still valid at the
    aggregate phase: product combine (the only mode exponentiating the
    per-node K's) with an exact-identity transmit phase — full-precision
    wire, no channel noise, no quantization — and no defense (the
    screened product re-scales quarantined uploads, so the factors of
    the raw K's must not short-circuit it)."""
    agg = strategies.get_aggregation(cfg.aggregation)
    return (agg.combine == "product" and agg.wire_dtype is None
            and cfg.upload_noise == 0.0 and cfg.quantize_bits is None
            and cfg.defense is None)


def _transmit_impl(ks_all: List[jax.Array], key: jax.Array,
                   cfg: QuantumFedConfig) -> List[jax.Array]:
    """Node -> server wire: channel model, then the strategy's cast."""
    ch = fchannel.resolve_channel(cfg.upload_noise, cfg.quantize_bits)
    ks_all = ch(key, ks_all)
    agg = strategies.get_aggregation(cfg.aggregation)
    return strategies.wire_cast(ks_all, agg)


def _probe_fidelity(params: qnn.Params, probe, widths, impl):
    """Mean fidelity of ``params`` on the server's probe batch."""
    phi_in, phi_out = probe
    rho = qnn.outputs(params, phi_in, widths, impl=impl)
    return jnp.mean(qnn.batched_fidelity(phi_out, rho, impl=impl))


def _screen_uploads(params: qnn.Params, ks_all: List[jax.Array],
                    weights: jax.Array, eps, cfg: QuantumFedConfig, probe):
    """defense="screen": the behavioral defense for the non-commutative
    Eq. 6 product (order statistics have no meaning there). Each node's
    CANDIDATE model — its own update chain e^{i eps K_{n,k}} applied to
    the global params — is scored on the server's probe batch; uploads
    whose fidelity falls more than ``screen_tol`` below the pre-round
    baseline are quarantined: weight zeroed (mass renormalized over the
    survivors) and generators zeroed so a NaN payload cannot reach the
    eigh. A NaN candidate fidelity compares False and self-quarantines.
    Returns ``(clean_ks_all, new_weights, keep)``."""
    if probe is None:
        raise ValueError(
            "defense='screen' needs a server probe batch — drive the "
            "round through QuantumSubstrate (it passes its held-out test "
            "pairs) or pass probe=(phi_in, phi_out) explicitly")
    base = _probe_fidelity(params, probe, cfg.widths, cfg.impl)

    def one(ks_n):  # per-node slice of every layer's (I_l, m, d, d)
        cand = [_chain(us, ql.expm_herm(kn, eps), cfg.impl)
                for us, kn in zip(params, ks_n)]
        return _probe_fidelity(cand, probe, cfg.widths, cfg.impl)

    fids = jax.vmap(one)(ks_all)                 # (N_p,)
    keep = fids >= base - cfg.screen_tol         # NaN fid => False
    w = weights * keep.astype(weights.dtype)
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    clean = [jnp.where(keep.reshape((-1,) + (1,) * (ks.ndim - 1)),
                       ks, jnp.zeros((), ks.dtype)) for ks in ks_all]
    return clean, w, keep


def _clip_uploads(ks_all: List[jax.Array], weights: jax.Array,
                  clip_norm: float):
    """defense="clip": per-matrix Frobenius norm-clip of every uploaded
    generator; non-finite uploads are zeroed and de-weighted (their mass
    renormalized over the finite nodes). Returns ``(clean, weights)``."""
    fin = strategies.finite_nodes(ks_all)
    w = weights * fin.astype(weights.dtype)
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    clean = []
    for ks in ks_all:
        f = strategies.clip_factors(ks, clip_norm)  # (..., 1, 1) real
        fb = fin.reshape((-1,) + (1,) * (ks.ndim - 1))
        clean.append(jnp.where(fb, ks * f.astype(ks.real.dtype),
                               jnp.zeros((), ks.dtype)))
    return clean, w


@ql.matmul_precision("unitary")
def _aggregate_impl(params: qnn.Params, smom, ks_all: List[jax.Array],
                    weights: jax.Array, eps, server_beta,
                    cfg: QuantumFedConfig, server_opt: str, factors=None,
                    mesh=None, probe=None):
    """``_combine_impl``, then every new layer unitary takes one
    ``ql.unitary_polish`` step. Rounding in the update chain moves the
    layers off the unitary group a little every round and nothing pulls
    them back: in complex64, |UU†-I| grows by ~1e-6 a round on the CPU
    and faster on the TPU. The step keeps them at the dtype's rounding
    for any number of rounds."""
    new_params, new_smom = _combine_impl(
        params, smom, ks_all, weights, eps, server_beta, cfg, server_opt,
        factors=factors, mesh=mesh, probe=probe)
    return [ql.unitary_polish(us) for us in new_params], new_smom


def _combine_impl(params: qnn.Params, smom, ks_all: List[jax.Array],
                  weights: jax.Array, eps, server_beta,
                  cfg: QuantumFedConfig, server_opt: str, factors=None,
                  mesh=None, probe=None):
    """Strategy combine; with ``server_opt`` != "none" the averaged
    Hermitian generators K̄_k pass through server momentum first (state
    ``smom``: per-layer arrays, or None for the zero round-0 state).
    ``cfg.topology`` routes the combine through the two-level pod tree
    (sharded over the mesh's 'pod' axis when one is active).
    ``cfg.defense`` hardens the combine against hostile uploads (see
    ``strategies.DEFENSES``); ``probe`` is the server's (phi_in,
    phi_out) screening batch, required by defense="screen" only.
    Returns ``(new_params, new_smom)``."""
    agg = strategies.get_aggregation(cfg.aggregation)
    strategies.validate_defense(cfg.defense, agg.combine)
    topo = _topology_of(cfg)
    if topo is not None:
        strategies.partial_kind(agg)   # fail loudly for tree-less combines
    if agg.combine == "product":
        if cfg.defense == "screen":
            ks_all, weights, _ = _screen_uploads(params, ks_all, weights,
                                                 eps, cfg, probe)
            factors = None  # factor the SANITIZED K's, not the raw ones
        # no additive delta to smooth (FedSpec rejects server_opt here)
        return (aggregate_product(params, ks_all, weights, eps,
                                  impl=cfg.impl, factors=factors,
                                  topo=topo, mesh=mesh), None)
    if cfg.defense == "clip":
        # clipped uploads flow through the standard weighted mean below
        ks_all, weights = _clip_uploads(ks_all, weights, cfg.clip_norm)
    robust = cfg.defense in ("trimmed_mean", "median")
    if robust and topo is not None:
        raise ValueError(
            f"defense {cfg.defense!r} needs every upload at the server "
            "(order statistics do not decompose over pod partial sums) — "
            "topology='flat' only")
    # order statistics treat every valid node equally (data-volume
    # weights only gate VALIDITY: a 0-weight or non-finite upload never
    # enters the sort window)
    valid = ((weights > 0) & strategies.finite_nodes(ks_all)
             if robust else None)

    def k_mean(ks):
        if robust:
            return strategies.robust_combine(ks, valid, cfg.defense,
                                             cfg.trim_frac)
        return _mean_generators(ks, weights, topo, mesh)

    if server_opt == "none":
        if not robust:
            return (aggregate_average(params, ks_all, weights, eps,
                                      impl=cfg.impl, topo=topo, mesh=mesh),
                    None)
        new_params = []
        for us, ks in zip(params, ks_all):
            upd = ql.expm_herm(k_mean(ks), eps)  # (I_l, m_l, d, d)
            new_params.append(_chain(us, upd, cfg.impl))
        return new_params, None
    new_params, new_smom = [], []
    for i, (us, ks) in enumerate(zip(params, ks_all)):
        k_bar = k_mean(ks)
        m2, eff = fserver_opt.generator_step(
            server_opt, server_beta, None if smom is None else smom[i],
            k_bar)
        upd = ql.expm_herm(eff, eps)  # e^{i eps K_eff} stays unitary
        new_params.append(_chain(us, upd, cfg.impl))
        new_smom.append(m2)
    return new_params, new_smom


def _server_round_impl(params: qnn.Params, smom, dataset: QuantumDataset,
                       key: jax.Array, eta, eps, server_beta,
                       cfg: QuantumFedConfig, mesh=None,
                       server_opt: str = "none", probe=None):
    """Returns ``(new_params, new_smom, err_bound)`` — err_bound is the
    round's accumulated approximation-error certificate (the per-node
    bounds combined with the aggregation weights; a 0.0 scalar for exact
    configs, where its computation is dead code jit removes)."""
    k_sel, k_node, k_noise = jax.random.split(key, 3)
    sel, _, weights = _select_impl(dataset, k_sel, cfg)
    reuse = _factors_survive_wire(cfg)
    certify = _approx_on(cfg)
    out = _local_impl(params, dataset, sel, k_node, eta, eps, cfg, mesh,
                      with_factors=reuse, with_bound=certify)
    if reuse and certify:
        ks_all, factors, bounds = out
    elif reuse:
        (ks_all, factors), bounds = out, None
    elif certify:
        (ks_all, bounds), factors = out, None
    else:
        ks_all, factors, bounds = out, None, None
    ks_all = _transmit_impl(ks_all, k_noise, cfg)
    new_params, new_smom = _aggregate_impl(
        params, smom, ks_all, weights, eps, server_beta, cfg, server_opt,
        factors=factors, mesh=mesh, probe=probe)
    rdt = ql.real_dtype(ql.default_dtype())
    err_bound = (jnp.sum(weights.astype(rdt) * bounds.astype(rdt))
                 if certify else jnp.zeros((), rdt))
    return new_params, new_smom, err_bound


_server_round = functools.partial(
    jax.jit, static_argnames=("cfg", "mesh", "server_opt"))(
        _server_round_impl)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "server_opt"))
def _server_round_from(params, smom, dataset, base_key, t, eta, eps,
                       server_beta, cfg, mesh=None, server_opt="none",
                       probe=None):
    """``_server_round`` under the session's round key ``fold_in(base_key,
    t)``, folded inside the same program: a session's round in one
    launch."""
    return _server_round_impl(params, smom, dataset,
                              jax.random.fold_in(base_key, t), eta, eps,
                              server_beta, cfg, mesh, server_opt, probe)


@functools.partial(jax.jit, static_argnames=("cfg", "server_opt"))
def _server_round_stacked(params, smom, dataset, keys, eta, eps,
                          server_beta, probe, cfg, server_opt):
    body = lambda p, sm, ds, k, et, ep, sb, pr: _server_round_impl(
        p, sm, ds, k, et, ep, sb, cfg, None, server_opt, pr)
    return jax.vmap(body)(params, smom, dataset, keys, eta, eps,
                          server_beta, probe)


def server_round_stacked(params: qnn.Params, dataset: QuantumDataset,
                         keys: jax.Array, cfg: QuantumFedConfig, *,
                         smom=None, eta=None, eps=None,
                         server_opt: str = "none", server_beta=None,
                         probe=None):
    """One QuanFedPS round for a STACK of independent federations — the
    multi-tenant serving hot path (``repro.core.fed.serve``).

    Every traced argument carries a leading session axis S: ``params``
    is the usual per-layer list with each layer (S, m_l, d, d),
    ``dataset`` stacks each tenant's ``QuantumDataset`` (so tenants keep
    their own target unitaries and node data), ``keys`` is (S, 2) — one
    round key per session. ``eta`` / ``eps`` / ``server_beta`` may be
    scalars or (S,) vectors: they are TRACED, so tenants in one group
    may run different hyperparameters against the same compiled round
    (the group key — ``FedSpec.fingerprint()`` — excludes them). The
    structural cfg must be identical across the stack; fan-out is forced
    to "vmap" (a pod mesh shards nodes WITHIN one federation, not across
    tenants). Returns ``(new_params, new_smom, err_bounds)`` with the
    same leading axis; numerics match S independent ``server_round``
    calls to jit-boundary rounding (<= 1e-10 under x64 — gated in
    ``tests/test_fed_serve.py``).
    """
    fserver_opt.validate(server_opt)
    strategies.get_aggregation(cfg.aggregation)   # fail loudly pre-trace
    participation.validate(cfg.participation)
    static_cfg = cfg._replace(eta=0.0, eps=0.0, fanout="vmap")
    s = jnp.shape(keys)[0]
    rdt = ql.real_dtype(ql.default_dtype())

    def vec(v, default):
        v = default if v is None else v
        return jnp.broadcast_to(jnp.asarray(v, rdt), (s,))

    return _server_round_stacked(
        params, smom, dataset, jnp.asarray(keys), vec(eta, cfg.eta),
        vec(eps, cfg.eps), vec(server_beta, 0.9), probe, static_cfg,
        server_opt)


def _resolve_fanout(cfg: QuantumFedConfig) -> str:
    """Pick the fan-out OUTSIDE jit. The resolved mode travels in the
    static cfg and the mesh itself is a static arg of the round programs
    (Mesh is hashable), so a round traced mesh-less is never replayed
    for a mesh run, nor one mesh's shard_map trace for another mesh."""
    if cfg.fanout == "vmap":
        return "vmap"
    mesh = rules.current_mesh()
    axis = rules.fed_fanout_axis(mesh) if mesh is not None else None
    ok = axis is not None and cfg.nodes_per_round % mesh.shape[axis] == 0
    if cfg.fanout == "shard_map":
        if not ok:
            raise ValueError(
                "fanout='shard_map' needs an active `with mesh:` whose "
                "'fed_node' rule axis divides nodes_per_round")
        return "shard_map"
    if cfg.fanout != "auto":
        raise ValueError(f"unknown fanout {cfg.fanout!r}; use "
                         "'auto' | 'vmap' | 'shard_map'")
    # auto: shard only when the mesh actually has >1 pod to spread over
    return "shard_map" if ok and mesh.shape[axis] > 1 else "vmap"


def server_round(params: qnn.Params, dataset: QuantumDataset,
                 key: jax.Array, cfg: QuantumFedConfig) -> qnn.Params:
    """One QuanFedPS iteration: the canonical select -> local ->
    transmit -> aggregate phase composition, fused under one jit.

    eta/eps are split out of cfg and traced so hyperparameter sweeps
    reuse one compiled round; the structural fields stay static.
    """
    new_params, _ = server_round_opt(params, None, dataset, key, cfg)
    return new_params


def server_round_opt(params: qnn.Params, smom, dataset: QuantumDataset,
                     key: jax.Array, cfg: QuantumFedConfig,
                     server_opt: str = "none", server_beta: float = 0.9,
                     probe=None):
    """``server_round`` threading the server-optimizer momentum state:
    returns ``(new_params, new_smom)`` (``new_smom`` None when
    ``server_opt == "none"``). ``probe``: the server's (phi_in, phi_out)
    screening batch — required when ``cfg.defense == "screen"``."""
    fserver_opt.validate(server_opt)
    static_cfg, mesh = _round_statics(cfg)
    new_params, new_smom, _ = _server_round(
        params, smom, dataset, key, cfg.eta, cfg.eps, server_beta,
        static_cfg, mesh, server_opt, probe)
    return new_params, new_smom


def server_round_certified(params: qnn.Params, dataset: QuantumDataset,
                           key: jax.Array, cfg: QuantumFedConfig,
                           smom=None, server_opt: str = "none",
                           server_beta: float = 0.9, probe=None):
    """``server_round_opt`` that also surfaces the round's accumulated
    approximation-error certificate: returns ``(new_params, new_smom,
    err_bound)``. err_bound is a real scalar bounding the total max-abs
    deviation of this round's update matrices from the exact engine's
    (per-node bounds from ``qnn.update_matrices(with_bound=True)``
    combined with the Alg. 2 aggregation weights); exactly 0.0 when the
    approximate-rank knobs are off. Same jit cache entry as the plain
    round — the bound computation is dead code XLA strips when unused.
    """
    fserver_opt.validate(server_opt)
    static_cfg, mesh = _round_statics(cfg)
    return _server_round(params, smom, dataset, key, cfg.eta, cfg.eps,
                         server_beta, static_cfg, mesh, server_opt, probe)


def server_round_from(params: qnn.Params, dataset: QuantumDataset,
                      base_key: jax.Array, t, cfg: QuantumFedConfig,
                      smom=None, server_opt: str = "none",
                      server_beta: float = 0.9, probe=None):
    """``server_round_certified`` keyed by ``fold_in(base_key, t)`` —
    the session's RNG contract — with the fold inside the round program:
    no eager key derivation, and no launch of its own, before the round.
    Pass ``t`` as an ``np.int32`` so that every round reuses one compiled
    program. Bit-identical to ``server_round_certified(params, dataset,
    jax.random.fold_in(base_key, t), cfg, ...)``."""
    fserver_opt.validate(server_opt)
    static_cfg, mesh = _round_statics(cfg)
    return _server_round_from(params, smom, dataset, base_key, t, cfg.eta,
                              cfg.eps, server_beta, static_cfg, mesh,
                              server_opt, probe)


# Per-phase entry points: same bodies as the fused round, each under its
# own jit, for schedulers that interleave phases of DIFFERENT rounds
# (async buffering commits uploads born several dispatches ago;
# overlapped dispatch enqueues round t+1's fan-out before round t's
# aggregation). Numerics match the fused round to jit-boundary rounding
# (<= 1e-10 under x64 — gated in tests/test_fed_schedulers.py).

@functools.partial(jax.jit, static_argnames=("cfg",))
def _select_jit(dataset, key, cfg):
    return _select_impl(dataset, key, cfg)


def select_phase(dataset: QuantumDataset, key: jax.Array,
                 cfg: QuantumFedConfig):
    """Phase 1: ``(sel, pmask, weights)`` for one round."""
    static_cfg, _ = _round_statics(cfg)
    return _select_jit(dataset, key, static_cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "with_bound"))
def _local_jit(params, dataset, sel, key, eta, eps, cfg, mesh,
               with_bound=False):
    return _local_impl(params, dataset, sel, key, eta, eps, cfg, mesh,
                       with_bound=with_bound)


def local_phase(params: qnn.Params, dataset: QuantumDataset,
                sel: jax.Array, key: jax.Array, cfg: QuantumFedConfig,
                with_bound: bool = False):
    """Phase 2: the QuanFedNode fan-out; per-layer (N_p, I_l, m, d, d).
    ``with_bound=True`` returns ``(ks_all, bounds)`` with the per-node
    approximation certificates (N_p,) appended — the phased-protocol
    form of the fused round's err_bound."""
    static_cfg, mesh = _round_statics(cfg)
    return _local_jit(params, dataset, sel, key, cfg.eta, cfg.eps,
                      static_cfg, mesh, with_bound=with_bound)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _transmit_jit(ks_all, key, cfg):
    return _transmit_impl(ks_all, key, cfg)


def transmit_phase(ks_all: List[jax.Array], key: jax.Array,
                   cfg: QuantumFedConfig) -> List[jax.Array]:
    """Phase 3: channel model + strategy wire cast."""
    static_cfg, _ = _round_statics(cfg)
    return _transmit_jit(ks_all, key, static_cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "server_opt"))
def _aggregate_jit(params, smom, ks_all, weights, eps, server_beta, cfg,
                   mesh, server_opt, probe=None):
    return _aggregate_impl(params, smom, ks_all, weights, eps,
                           server_beta, cfg, server_opt, mesh=mesh,
                           probe=probe)


def aggregate_phase(params: qnn.Params, ks_all: List[jax.Array],
                    weights: jax.Array, cfg: QuantumFedConfig,
                    smom=None, server_opt: str = "none",
                    server_beta: float = 0.9, probe=None):
    """Phase 4: strategy combine into the global model; returns
    ``(new_params, new_smom)``. ``ks_all`` may stack ANY number of
    uploads (async commits K of a cohort's N_p) — under a two-level
    topology the stack height must still split into ``cfg.pods`` equal
    pods (spec validation gates the async commit size). ``probe``: the
    server's screening batch for ``cfg.defense == "screen"``."""
    fserver_opt.validate(server_opt)
    static_cfg, mesh = _round_statics(cfg)
    return _aggregate_jit(params, smom, ks_all, weights, cfg.eps,
                          server_beta, static_cfg, mesh, server_opt,
                          probe)


def _round_statics(cfg: QuantumFedConfig):
    """The static (cfg, mesh) pair the round programs are traced under —
    eta/eps zeroed out of the cache key, fanout resolved against the
    ambient mesh. Shared by the round entries and ``lower_server_round``
    so dryruns lower exactly the trace training executes."""
    strategies.get_aggregation(cfg.aggregation)   # fail loudly pre-trace
    participation.validate(cfg.participation)
    fanout = _resolve_fanout(cfg)
    mesh = rules.current_mesh() if fanout == "shard_map" else None
    return cfg._replace(eta=0.0, eps=0.0, fanout=fanout), mesh


def lower_server_round(params: qnn.Params, dataset: QuantumDataset,
                       key: jax.Array, cfg: QuantumFedConfig):
    """Lower (not run) one round under the ambient mesh — the dryrun /
    benchmark hook. It lowers the program a session's sync round runs
    (``server_round_from``: base ``key`` and an int32 round index in,
    the fold inside), under the same static-cfg protocol as training."""
    static_cfg, mesh = _round_statics(cfg)
    return _server_round_from.lower(params, None, dataset, key,
                                    np.int32(0), cfg.eta, cfg.eps, 0.0,
                                    static_cfg, mesh, "none")


@functools.partial(jax.jit, static_argnames=("widths", "impl"))
@ql.matmul_precision("ensemble")
def evaluate(params: qnn.Params, phi_in: jax.Array, phi_out: jax.Array,
             widths: Tuple[int, ...], impl: str = "xla",
             weights: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """Mean fidelity / MSE; `weights` masks out padded invalid pairs.
    Both metrics honor ``impl`` (fidelity AND mse Pallas kernels)."""
    rho_out = qnn.outputs(params, phi_in, widths, impl=impl)
    fid = qnn.batched_fidelity(phi_out, rho_out, impl=impl)
    mse = qnn.batched_mse(phi_out, rho_out, impl=impl)
    if weights is None:
        return {"fidelity": jnp.mean(fid), "mse": jnp.mean(mse)}
    w = weights.astype(fid.dtype)
    denom = jnp.maximum(jnp.sum(w), 1e-12)
    return {"fidelity": jnp.sum(w * fid) / denom,
            "mse": jnp.sum(w * mse) / denom}


def train(key: jax.Array, cfg: QuantumFedConfig, dataset: QuantumDataset,
          test: Tuple[jax.Array, jax.Array], n_iterations: int,
          params: Optional[qnn.Params] = None, eval_every: int = 1,
          verbose: bool = False) -> Tuple[qnn.Params, Dict[str, list]]:
    """DEPRECATED parity shim over ``repro.core.fed.api`` — prefer
    ``FederationSession`` (checkpointable, resumable, hookable).

    Drives a session with the legacy key schedule (init split + the
    ``split(k_loop, n_iterations)`` round-key plan) and eval cadence, so
    the returned (params, history) match the pre-session loop
    bit-for-bit. Metric records cost ONE host sync each (a single
    ``jax.device_get``), not one blocking ``float(...)`` per metric.
    """
    import warnings

    from repro.core.fed import api

    warnings.warn("fed.train is a legacy shim; use repro.core.fed.api."
                  "FederationSession", DeprecationWarning, stacklevel=2)
    spec = api.FedSpec.from_quantum_config(cfg)
    sub = api.QuantumSubstrate(spec, dataset=dataset, test=test)
    sess = api.FederationSession.create(spec, key, substrate=sub,
                                        params=params, rounds=n_iterations)
    sess.run(n_iterations,
             callbacks=[api.EvalEvery(eval_every, verbose=verbose)])
    return sess.state, sess.history
