"""Plain reference of the dissipative QNN federation the benchmark runs.

The network is Beer et al.'s dissipative quantum neural network (Nat.
Commun. 11, 808, 2020) as QuantumFed trains it (arXiv 2106.09109):
widths ``(m_0, ..., m_L)``; layer ``l`` holds ``m_l`` perceptron
unitaries of dimension ``2**(m_{l-1}+1)``, perceptron ``j`` acting on
every input qubit and output qubit ``j``. One federation round is

* Alg. 2 selection: ``N_p`` of ``N`` nodes uniformly without
  replacement, weights ``w_n = N_n / N_t``;
* Alg. 1 on every selected node: ``I_l`` steps of the Prop.-1 update
  ``U_j <- exp(i eps K_j) U_j`` with
  ``K_j = eta 2**m_in i / N sum_x tr_rest [A_j, B_j]``;
* Eq. 6 (product): ``U <- prod_{k=I_l..1} prod_n exp(i eps w_n K_{n,k}) U``,
  or Eq. 8 (average): ``U <- prod_k exp(i eps sum_n w_n K_{n,k}) U``.

Everything here is written out in the full ``2**(m_in+m_out)`` layer
space with dense embedded perceptrons, the way the paper states it, in
``jax.numpy`` complex64 with every contraction at ``highest``. The
exponential is a Taylor series with scaling and squaring (no
eigendecomposition). It imports nothing of the system under test.

Departures from the paper: none in the mathematics. The key schedule
(which node a round selects) follows the federation session's stated
RNG contract, ``round_key(t) = fold_in(split(key)[1], t)`` and
``(k_select, k_node, k_channel) = split(round_key, 3)``, and the uniform
draw is ``jax.random.choice(k_select, N, (N_p,), replace=False)``; a
reference that drew other nodes would train on other data.

``Reference(..., precision=...)`` lowers the precision of the matmuls
by role, as the benchmark's control: ``"high"`` / ``"default"`` are
the chip's own three- and one-pass bfloat16 products.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# the chip's own matmul passes: six, three or one bfloat16 pass (the
# CPU computes all three in float32)
PASSES = {"highest": HIGHEST, "high": jax.lax.Precision.HIGH,
          "default": jax.lax.Precision.DEFAULT}
# roles as the configuration states them: "unitary" forms and applies
# update unitaries (exponential, Eq. 6 chain); "ensemble" is the
# Prop.-1 forward / backward pass and the evaluation
FULL = {"unitary": "highest", "ensemble": "highest"}


def ein(spec: str, a, b, mode: str):
    """A contraction of two complex operands at the given precision."""
    return jnp.einsum(spec, a, b, precision=PASSES[mode])


def dagger(a):
    return jnp.conjugate(jnp.swapaxes(a, -1, -2))


def embed(u, acting: Sequence[int], n: int):
    """``u`` on the qubits ``acting`` (in that order), identity elsewhere,
    as a dense ``2**n`` matrix; qubit 0 is the most significant."""
    rest = [q for q in range(n) if q not in acting]
    full = jnp.kron(u, jnp.eye(2 ** len(rest), dtype=u.dtype))
    order = list(acting) + rest
    perm = [order.index(q) for q in range(n)]
    t = full.reshape((2,) * (2 * n))
    t = jnp.transpose(t, perm + [n + p for p in perm])
    return t.reshape(2 ** n, 2 ** n)


def partial_trace(rho, keep: Sequence[int], n: int):
    """Trace out every qubit not in ``keep`` (ascending); batched."""
    batch = rho.shape[:-2]
    rest = [q for q in range(n) if q not in keep]
    nb = len(batch)
    t = rho.reshape(batch + (2,) * (2 * n))
    perm = (list(range(nb)) + [nb + q for q in keep] + [nb + q for q in rest]
            + [nb + n + q for q in keep] + [nb + n + q for q in rest])
    dk, dr = 2 ** len(keep), 2 ** len(rest)
    t = jnp.transpose(t, perm).reshape(batch + (dk, dr, dk, dr))
    return jnp.trace(t, axis1=nb + 1, axis2=nb + 3)


def sandwich(u, rho, mode):
    """u rho u† for a batch of rho."""
    return ein("...ab,bc->...ac", ein("ab,...bc->...ac", u, rho, mode),
               dagger(u), mode)


def acting(m_in: int, j: int) -> List[int]:
    return list(range(m_in)) + [m_in + j]


def _kron_zero(rho, d_out):
    """rho ⊗ |0..0><0..0| (batched)."""
    d_in = rho.shape[-1]
    full = jnp.zeros(rho.shape[:-2] + (d_in, d_out, d_in, d_out), rho.dtype)
    full = full.at[..., :, 0, :, 0].set(rho)
    return full.reshape(rho.shape[:-2] + (d_in * d_out, d_in * d_out))


def _kron_eye(sigma, d_in):
    """I ⊗ sigma (batched)."""
    eye = jnp.eye(d_in, dtype=sigma.dtype)
    d_out = sigma.shape[-1]
    full = eye[:, None, :, None] * sigma[..., None, :, None, :]
    return full.reshape(sigma.shape[:-2] + (d_in * d_out, d_in * d_out))


def expm_i(h, mode):
    """exp(i h) for Hermitian h (batched): Taylor series of order 12 on
    i h / 2**s, squared s times, with s chosen per matrix so that the
    scaled Frobenius norm is at most 1/2."""
    x = 1j * h
    nrm = jnp.sqrt(jnp.sum(jnp.abs(x) ** 2, axis=(-2, -1)))
    s = jnp.maximum(0, jnp.ceil(jnp.log2(jnp.maximum(nrm, 1e-30) / 0.5)))
    s = s.astype(jnp.int32)
    y = x / (2.0 ** s.astype(jnp.float32))[..., None, None].astype(x.dtype)
    eye = jnp.broadcast_to(jnp.eye(h.shape[-1], dtype=h.dtype), h.shape)
    t = eye
    for k in range(12, 0, -1):
        t = eye + ein("...ab,...bc->...ac", y, t, mode) / k

    def square(i, t):
        sq = ein("...ab,...bc->...ac", t, t, mode)
        live = (i < s)[..., None, None]
        return jnp.where(live, sq, t)

    return jax.lax.fori_loop(0, jnp.max(s), square, t)


class Reference:
    """The reference federation for one configuration (see module
    docstring). ``precision`` maps each role to a key of ``PASSES``."""

    def __init__(self, widths: Sequence[int], eta: float, eps: float,
                 precision: Dict[str, str] = FULL):
        self.widths = tuple(int(w) for w in widths)
        self.eta = float(eta)
        self.eps = float(eps)
        self.prec = dict(precision)
        for role in ("unitary", "ensemble"):
            if self.prec.get(role) not in PASSES:
                raise ValueError(
                    f"precision[{role!r}] must be one of {sorted(PASSES)}")
        self._round_fn = {}

    # -- the network ------------------------------------------------------
    def _embedded(self, us, m_in, m_out):
        n = m_in + m_out
        return [embed(us[j], acting(m_in, j), n) for j in range(m_out)]

    def feedforward(self, params, rho):
        mode = self.prec["ensemble"]
        rhos = [rho]
        for l in range(1, len(self.widths)):
            m_in, m_out = self.widths[l - 1], self.widths[l]
            n = m_in + m_out
            full = _kron_zero(rhos[-1], 2 ** m_out)
            for e in self._embedded(params[l - 1], m_in, m_out):
                full = sandwich(e, full, mode)
            rhos.append(partial_trace(full, list(range(m_in, n)), n))
        return rhos

    def backward(self, params, sigma):
        mode = self.prec["ensemble"]
        sigmas = [sigma]
        for l in range(len(self.widths) - 1, 0, -1):
            m_in, m_out = self.widths[l - 1], self.widths[l]
            d_in, d_out = 2 ** m_in, 2 ** m_out
            full = _kron_eye(sigmas[-1], d_in)
            for e in self._embedded(params[l - 1], m_in, m_out)[::-1]:
                full = sandwich(dagger(e), full, mode)
            t = full.reshape(sigma.shape[:-2] + (d_in, d_out, d_in, d_out))
            sigmas.append(t[..., :, 0, :, 0])
        return sigmas[::-1]

    def update_matrices(self, params, phi_in, phi_out):
        """Prop. 1: the update matrices K of every perceptron, per layer
        (m_l, d, d), from one node's pairs."""
        mode = self.prec["ensemble"]
        rho = phi_in[:, :, None] * jnp.conjugate(phi_in[:, None, :])
        sig = phi_out[:, :, None] * jnp.conjugate(phi_out[:, None, :])
        n_pairs = phi_in.shape[0]
        rhos = self.feedforward(params, rho)
        sigmas = self.backward(params, sig)
        ks = []
        for l in range(1, len(self.widths)):
            m_in, m_out = self.widths[l - 1], self.widths[l]
            n = m_in + m_out
            emb = self._embedded(params[l - 1], m_in, m_out)
            a = _kron_zero(rhos[l - 1], 2 ** m_out)
            b = _kron_eye(sigmas[l], 2 ** m_in)
            bs = [b]
            for jj in range(m_out - 1, 0, -1):
                b = sandwich(dagger(emb[jj]), b, mode)
                bs.append(b)
            bs = bs[::-1]
            layer = []
            for j in range(m_out):
                a = sandwich(emb[j], a, mode)
                m = (ein("xab,xbc->xac", a, bs[j], mode)
                     - ein("xab,xbc->xac", bs[j], a, mode))
                t = partial_trace(m, acting(m_in, j), n)
                layer.append((self.eta * 2.0 ** m_in * 1j / n_pairs)
                             * jnp.sum(t, axis=0))
            ks.append(jnp.stack(layer))
        return ks

    def apply(self, params, ks, scale):
        mode = self.prec["unitary"]
        return [ein("jab,jbc->jac", expm_i(scale * k, mode), u, mode)
                for u, k in zip(params, ks)]

    def node_update(self, params, phi_in, phi_out, steps: int):
        """Alg. 1: ``steps`` local Prop.-1 steps; returns the K of every
        step, per layer (steps, m_l, d, d)."""
        def one(p, _):
            ks = self.update_matrices(p, phi_in, phi_out)
            return self.apply(p, ks, self.eps), ks
        _, ks = jax.lax.scan(one, list(params), None, length=steps)
        return ks

    def fidelity(self, params, phi_in, phi_out, chunk: int = 64):
        """Mean fidelity <phi_out| rho_L |phi_out> over the pairs."""
        n = phi_in.shape[0]
        chunk = min(chunk, n)
        pad = (-n) % chunk
        pin = jnp.concatenate([phi_in, phi_in[:pad]]) if pad else phi_in
        pout = jnp.concatenate([phi_out, phi_out[:pad]]) if pad else phi_out

        def one(args):
            xi, xo = args
            rho = xi[:, :, None] * jnp.conjugate(xi[:, None, :])
            r = self.feedforward(params, rho)[-1]
            return jnp.real(jnp.einsum("xa,xab,xb->x", jnp.conjugate(xo), r,
                                       xo, precision=HIGHEST))

        f = jax.lax.map(one, (pin.reshape((-1, chunk) + pin.shape[1:]),
                              pout.reshape((-1, chunk) + pout.shape[1:])))
        return jnp.mean(f.reshape(-1)[:n])

    # -- one federation round ---------------------------------------------
    def round(self, params, phi_in, phi_out, round_key, *, nodes_per_round,
              interval_length, aggregation, keep_nodes=None, renorm=True):
        """One QuanFedPS round on per-node data (N, n_per, d). The
        ``keep_nodes`` / ``renorm`` arguments plant the benchmark's
        faults: only the first ``keep_nodes`` selected nodes reach the
        aggregate, with their weights renormalized or not."""
        key = (int(nodes_per_round), int(interval_length), aggregation,
               keep_nodes, bool(renorm))
        fn = self._round_fn.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(self._round, cfg=key))
            self._round_fn[key] = fn
        return fn(list(params), phi_in, phi_out, round_key)

    def _round(self, params, phi_in, phi_out, round_key, cfg):
        n_p, i_l, aggregation, keep, renorm = cfg
        num_nodes = phi_in.shape[0]
        k_sel, _, _ = jax.random.split(round_key, 3)
        sel = jax.random.choice(k_sel, num_nodes, (n_p,), replace=False)
        weights = jnp.full((n_p,), 1.0 / n_p, jnp.float32)
        if keep is not None:
            sel = sel[:keep]
            weights = (jnp.full((keep,), 1.0 / keep, jnp.float32) if renorm
                       else weights[:keep])
        ks = jax.lax.map(
            lambda s: self.node_update(params, phi_in[s], phi_out[s], i_l),
            sel)                                   # per layer (n, I_l, m, d, d)
        mode = self.prec["unitary"]
        new = []
        for us, k in zip(params, ks):
            if aggregation == "product":
                scaled = k * weights[:, None, None, None, None].astype(k.dtype)
                seq = jnp.swapaxes(scaled, 0, 1).reshape((-1,) + k.shape[2:])
            elif aggregation == "average":
                seq = jnp.einsum("n,nk...->k...", weights.astype(k.dtype), k)
            else:
                raise ValueError(f"unknown aggregation {aggregation!r}")

            def chain(u, kk):
                return ein("jab,jbc->jac", expm_i(self.eps * kk, mode), u,
                           mode), None
            u, _ = jax.lax.scan(chain, us, seq)
            new.append(u)
        return new


def session_round_key(key, t: int):
    """The federation session's round key for round ``t``."""
    return jax.random.fold_in(jax.random.split(key)[1], t)


def train(ref: Reference, params, phi_in, phi_out, key, rounds: int,
          **round_kw) -> Tuple[list, list]:
    """``rounds`` rounds from ``params``; returns every state, the
    first being ``params``."""
    states = [list(params)]
    for t in range(rounds):
        states.append(ref.round(states[-1], phi_in, phi_out,
                                session_round_key(key, t), **round_kw))
    return states

