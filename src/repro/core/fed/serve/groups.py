"""Session grouping — WHO shares a compiled stacked round.

``FedSpec.fingerprint()`` hashes the group-relevant spec fields (QNN
widths, cohort shape, strategy names, engine/impl/rank knobs — not
traced hyperparameters, not data content), so sessions with equal
fingerprints trace to the SAME compiled federation round. A
``StackedGroup`` seats such sessions on a fixed grid of S slots and
drives every occupied slot's next round as ONE
``federated.server_round_stacked`` call over the leading session axis:
per-slot state lives RESIDENT in stacked device buffers (admission
scatters a session in, retirement gathers it out — the grid is never
re-stacked per tick), per-slot round keys are ``fold_in(base_key,
round)`` under the session RNG contract (``repro.core.fed.api.
session``), folded in the program as a sync session's own round does,
and idle slots compute but their results are masked out (the
fixed-shape price of continuous batching, same as the decode
scheduler's frozen caches).

Sessions the stacked path cannot drive — classical substrates (their
round pulls host-side data pools), async/overlapped schedules (their
in-flight buffers are per-session host state), sessions pinned to an
explicit round-key plan — fall back to a ``SequentialGroup``: the same
admission grid, one ``session.step()`` per active slot per tick. The
server routes by ``group_mode``; a serving deployment typically runs
both kinds side by side.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.fed.api.session import FederationSession
from repro.core.fed.api.spec import FedSpec
from repro.core.fed.serve.admission import SlotGrid


def group_mode(spec: FedSpec,
               session: Optional[FederationSession] = None) -> str:
    """"stacked" when the spec's rounds can run as one vmapped call —
    quantum substrate, sync schedule, fold-in round keys — else
    "sequential"."""
    if spec.substrate != "quantum" or spec.schedule != "sync":
        return "sequential"
    if spec.fault_model is not None or spec.round_deadline is not None:
        # the robust sync path (fault effects, deadline retries) is a
        # host-side per-session loop — not expressible as one vmapped
        # round body
        return "sequential"
    if session is not None and session.round_keys is not None:
        return "sequential"  # explicit key plans are per-session state
    return "stacked"


def group_key(spec: FedSpec,
              session: Optional[FederationSession] = None) -> str:
    """The routing key: fingerprint + execution mode."""
    return f"{spec.fingerprint()}:{group_mode(spec, session)}"


def _tile(x: jax.Array, s: int) -> jax.Array:
    """Replicate a leaf along a fresh leading slot axis."""
    x = jnp.asarray(x)
    return jnp.broadcast_to(x[None], (s,) + x.shape)


@functools.partial(jax.jit, donate_argnums=(0,))
def _slot_write(bufs, vals, i: jax.Array):
    """Scatter one session's whole state pytree into slot ``i`` of the
    stacked buffers as ONE dispatch. The slot index is TRACED — a
    Python-int index would specialize the compile cache per slot (S
    compiles, ~35ms each) — and fusing the ~8 per-buffer scatters into
    one call keeps seating (~0.1ms) well under a solo round (~0.5ms),
    which matters when every tenant is seated exactly once per visit."""
    return jax.tree.map(
        lambda b, x: jax.lax.dynamic_update_index_in_dim(
            b, jnp.asarray(x).astype(b.dtype), i, 0), bufs, vals)


@jax.jit
def _slot_read(bufs, i: jax.Array):
    """Gather slot ``i``'s state pytree out in one dispatch (same
    traced-index cache story)."""
    return jax.tree.map(
        lambda b: jax.lax.dynamic_index_in_dim(b, i, 0, keepdims=False),
        bufs)


@jax.jit
def _slot_finite(params):
    """(S,) bool: every layer buffer of the slot is fully finite —
    ``jnp.isfinite`` on complex is finite-in-both-parts."""
    fin = None
    for p in params:
        f = jnp.all(jnp.isfinite(p).reshape(p.shape[0], -1), axis=1)
        fin = f if fin is None else (fin & f)
    return fin


def _state_finite(session) -> bool:
    """True when every inexact leaf of the session state is finite."""
    for x in jax.tree.leaves(session.state):
        x = jnp.asarray(x)
        if (jnp.issubdtype(x.dtype, jnp.inexact)
                and not bool(jnp.all(jnp.isfinite(x)))):
            return False
    return True


@functools.partial(jax.jit,
                   static_argnames=("cfg", "server_opt", "k"),
                   donate_argnums=(0, 1, 2))
def _serve_tick(params, smom, err, data, base_keys, rounds, active,
                targets, eta, eps, beta, probe, cfg, server_opt, k):
    """One WHOLE serving tick as a single dispatch: a ``lax.scan`` of
    ``k`` federation rounds, each with per-slot round keys
    (``fold_in(base, t)`` — the session RNG contract, which a sync
    session's round folds in its program too, so a session sees the
    same key stream stacked as it would stepping alone) and a live-mask
    merge that freezes idle slots AND
    slots whose round budget ran out mid-scan: a slot advances exactly
    ``min(k, target - round)`` rounds, then coasts with its updates
    discarded (the fixed-shape price of batching, like the decode
    scheduler's inactive cache writes). ``k > 1`` amortizes dispatch +
    host transfers over k rounds per tick — the multi-step serving
    knob — at the cost of admission latency (freed slots re-admit at
    tick boundaries). The state buffers are DONATED — outputs alias
    the grid's residents in place instead of reallocating the whole
    grid every tick; callers must (and the group does) drop their old
    references on return."""
    from repro.core.quantum import federated as fed

    def body(carry, _):
        params, smom, err, rounds = carry
        live = active & (rounds < targets)
        keys = jax.vmap(jax.random.fold_in)(base_keys, rounds)
        new_p, new_m, err_r = fed.server_round_stacked(
            params, data, keys, cfg, smom=smom, eta=eta, eps=eps,
            server_opt=server_opt, server_beta=beta, probe=probe)

        def mrg(n, o):
            m = live.reshape((-1,) + (1,) * (n.ndim - 1))
            return jnp.where(m, n, o)

        params = jax.tree.map(mrg, new_p, params)
        if smom is not None:
            smom = jax.tree.map(mrg, new_m, smom)
        if err is not None:
            err = jnp.where(live, err + err_r, err)
        rounds = rounds + live.astype(rounds.dtype)
        return (params, smom, err, rounds), None

    (params, smom, err, _), _ = jax.lax.scan(
        body, (params, smom, err, rounds), None, length=k)
    return params, smom, err


class StackedGroup:
    """S compiled slots driving same-fingerprint quantum sessions, up
    to ``rounds_per_tick`` stacked rounds per tick (module docstring)."""

    mode = "stacked"

    def __init__(self, spec: FedSpec, n_slots: int,
                 rounds_per_tick: int = 1):
        from repro.core.quantum import linalg as ql

        self.spec = spec  # structural template (fingerprint fields)
        self.grid = SlotGrid(n_slots)
        self.rounds_per_tick = rounds_per_tick
        self.cfg = spec.to_quantum_config()
        self.with_smom = spec.server_opt != "none"
        self.certified = ql.resolve_approx(
            spec.rank_tol, spec.rank_cap, spec.ensemble_dtype) is not None
        self.sessions: Dict[int, FederationSession] = {}
        # host-side per-slot scalars + stacked device residents — all
        # lazily shaped by the first seat (the grid's width materializes
        # at first admission, sized to the queue actually present)
        self.rounds = None    # (S,) absolute session rounds
        self._targets = None  # (S,) absolute round budgets
        self._eta = None      # (S,) per-tenant hyperparameters
        self._eps = None
        self._beta = None
        self._params = None   # per-layer list, each (S, m_l, d, d)
        self._smom = None     # per-layer list, each (S, I_l, m_l, d, d)
        self._err = None      # (S,) running certificates
        self._data = None     # stacked QuantumDataset
        self._keys = None     # (S, 2) uint32 base keys
        self._probe = None    # stacked screening batch (defense="screen")
        # (slot, diagnostic) pairs the server quarantines after a tick
        self._faulted = []

    # -- seating --------------------------------------------------------
    def _init_buffers(self, session: FederationSession) -> None:
        """First seat shapes the whole grid (tile one session's state)."""
        params, smom, err = session.substrate.state_parts(session.state)
        s = self.grid.n_slots
        spec = self.spec
        self.rounds = np.zeros(s, np.int64)
        self._targets = np.zeros(s, np.int64)
        self._eta = np.full(s, spec.eta, np.float64)
        self._eps = np.full(s, spec.eps, np.float64)
        self._beta = np.full(s, spec.server_momentum, np.float64)
        self._params = [_tile(p, s) for p in params]
        if self.with_smom:
            self._smom = [_tile(m, s) for m in smom]
        if self.certified:
            self._err = jnp.zeros((s,), jnp.asarray(err).dtype)
        self._data = jax.tree.map(lambda x: _tile(x, s),
                                  session.substrate.dataset)
        self._keys = _tile(jnp.asarray(session.key), s)
        probe = getattr(session.substrate, "_probe", None)
        if probe is not None:
            self._probe = jax.tree.map(lambda x: _tile(x, s), probe)

    def seat(self, slot: int, session: FederationSession,
             target: Optional[int] = None) -> None:
        """Scatter a session's state into its slot's stacked buffers —
        ONE ``_slot_write`` dispatch over the whole buffer pytree, slot
        index traced, so seating any slot hits one compiled scatter
        that is shape-stable however admission churns. ``target`` is
        the absolute round budget (the slot stops advancing there when
        ticks run multiple rounds); None means unbounded."""
        if self._params is None:
            self._init_buffers(session)
        params, smom, err = session.substrate.state_parts(session.state)
        bufs = (self._params, self._smom, self._err, self._data,
                self._keys, self._probe)
        vals = (list(params),
                list(smom) if self.with_smom else None,
                err if self.certified else None,
                session.substrate.dataset,
                jnp.asarray(session.key),
                (getattr(session.substrate, "_probe", None)
                 if self._probe is not None else None))
        (self._params, self._smom, self._err, self._data,
         self._keys, self._probe) = _slot_write(bufs, vals, np.int32(slot))
        self.rounds[slot] = session.round
        # sentinel survives the int32 device cast in step()
        self._targets[slot] = (np.iinfo(np.int32).max if target is None
                               else target)
        self._eta[slot] = session.spec.eta
        self._eps[slot] = session.spec.eps
        self._beta[slot] = session.spec.server_momentum
        self.sessions[slot] = session

    def seat_many(self, claims) -> None:
        for slot, session, target in claims:
            self.seat(slot, session, target)

    def sync_out(self, slot: int) -> None:
        """Gather a slot's stacked state back into its session object
        (exact array reads — park/revive after a sync is bit-exact)."""
        session = self.sessions[slot]
        params, smom, err = _slot_read(
            (self._params, self._smom, self._err), np.int32(slot))
        session.state = session.substrate.pack_state(params, smom, err)
        session.round = int(self.rounds[slot])

    def unseat(self, slot: int) -> str:
        """Gather state out and free the slot for the next queued
        session (the buffers keep the retired state as inert filler)."""
        self.sync_out(slot)
        del self.sessions[slot]
        return self.grid.free(slot)

    def round_of(self, slot: int) -> int:
        return int(self.rounds[slot])

    # -- the stacked round ---------------------------------------------
    def step(self) -> int:
        """Up to ``rounds_per_tick`` rounds for every occupied slot —
        ONE fused dispatch (``_serve_tick``: scanned fold-in keys +
        stacked rounds + live-mask merges). The host round mirror
        advances by exactly what the device scan did: ``min(k, target -
        round)`` per active slot."""
        active = self.grid.active_mask()
        n = int(active.sum())
        if n == 0:
            return 0
        k = self.rounds_per_tick
        self._params, self._smom, self._err = _serve_tick(
            self._params, self._smom, self._err, self._data, self._keys,
            jnp.asarray(self.rounds, jnp.int32), jnp.asarray(active),
            jnp.asarray(self._targets, jnp.int32), jnp.asarray(self._eta),
            jnp.asarray(self._eps), jnp.asarray(self._beta), self._probe,
            self.cfg, self.spec.server_opt, k)
        self.rounds[active] = np.minimum(self.rounds[active] + k,
                                         self._targets[active])
        # failure isolation: a slot whose model went non-finite (corrupt
        # data, numerical blow-up) is flagged for the server to
        # quarantine — the vmapped tick already kept it from touching
        # any other slot's buffers
        with obs.span("qfed.fetch"):
            fin = np.asarray(jax.device_get(_slot_finite(self._params)))
        for slot in np.nonzero(active & ~fin)[0]:
            self._faulted.append(
                (int(slot), "non-finite model state after stacked tick"))
        return n

    def take_faulted(self):
        """Drain the (slot, diagnostic) pairs flagged by ``step``."""
        out, self._faulted = self._faulted, []
        return out


class SequentialGroup:
    """Fallback execution: the same slot grid, up to ``rounds_per_tick``
    ``session.step()`` calls per active slot per tick (classical
    substrates, async/overlapped schedules, explicit round-key plans)."""

    mode = "sequential"

    def __init__(self, spec: FedSpec, n_slots: int,
                 rounds_per_tick: int = 1):
        self.spec = spec
        self.grid = SlotGrid(n_slots)
        self.rounds_per_tick = rounds_per_tick
        self.sessions: Dict[int, FederationSession] = {}
        self._targets: Dict[int, Optional[int]] = {}
        self._faulted: List[Tuple[int, str]] = []

    def seat(self, slot: int, session: FederationSession,
             target: Optional[int] = None) -> None:
        self.sessions[slot] = session
        self._targets[slot] = target

    def seat_many(self, claims) -> None:
        for slot, session, target in claims:
            self.seat(slot, session, target)

    def sync_out(self, slot: int) -> None:
        pass  # the session object IS the live state

    def unseat(self, slot: int) -> str:
        del self.sessions[slot]
        self._targets.pop(slot, None)
        return self.grid.free(slot)

    def round_of(self, slot: int) -> int:
        return self.sessions[slot].round

    def step(self) -> int:
        n = 0
        check_finite = self.spec.fault_model is not None
        for slot, sid in enumerate(self.grid.sid):
            if sid is None:
                continue
            if any(slot == s for s, _ in self._faulted):
                continue  # already flagged; server will quarantine it
            session = self.sessions[slot]
            target = self._targets.get(slot)
            todo = self.rounds_per_tick
            if target is not None:
                todo = min(todo, max(target - session.round, 0))
            try:
                for _ in range(todo):
                    session.step()
            except RuntimeError as e:
                # deadline/retry exhaustion or commit starvation: isolate
                # this session, keep serving the rest of the grid
                self._faulted.append((slot, f"{type(e).__name__}: {e}"))
                continue
            if check_finite and not _state_finite(session):
                self._faulted.append(
                    (slot, "non-finite model state after step"))
                continue
            n += 1
        return n

    def take_faulted(self):
        """Drain the (slot, diagnostic) pairs flagged by ``step``."""
        out, self._faulted = self._faulted, []
        return out


def make_group(spec: FedSpec, mode: str, n_slots: int,
               rounds_per_tick: int = 1):
    if mode == "stacked":
        return StackedGroup(spec, n_slots, rounds_per_tick)
    return SequentialGroup(spec, n_slots, rounds_per_tick)
