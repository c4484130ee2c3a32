"""Inputs of a cell, made from ``--seed``: the federation's data and the
network's initial weights.

QuantumFed's data (arXiv 2106.09109, §IV-A): a Haar-random target
unitary on the input qubits; training pairs ``(|phi>, U|phi>)`` with
Haar-random inputs, sorted by the phase of their first amplitude and
split contiguously over the nodes (the paper's non-iid split); clean
test pairs of the same target. Weights: every perceptron unitary
Haar-random. Everything is made on the device in one jitted call, in
complex64, the type the program trains in.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (JAX's own seed
    keeps only the low 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


class Federation(NamedTuple):
    """One federation's inputs. ``phi_in``/``phi_out``: (N, n_per, d_in);
    ``test``: two (n_test, d_in) arrays; ``params``: per layer
    (m_l, 2**(m_{l-1}+1), ...)."""
    phi_in: jax.Array
    phi_out: jax.Array
    test_in: jax.Array
    test_out: jax.Array
    params: Tuple[jax.Array, ...]


def _haar_states(key, n, d):
    kr, ki = jax.random.split(key)
    psi = (jax.random.normal(kr, (n, d)) + 1j * jax.random.normal(ki, (n, d)))
    psi = psi.astype(jnp.complex64)
    return psi / jnp.linalg.norm(psi, axis=-1, keepdims=True)


def _haar_unitaries(key, batch, d):
    kr, ki = jax.random.split(key)
    shape = (batch, d, d)
    z = (jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape))
    q, r = jnp.linalg.qr(z.astype(jnp.complex64) / jnp.sqrt(2.0))
    diag = jnp.diagonal(r, axis1=-2, axis2=-1)
    return q * (diag / jnp.abs(diag))[..., None, :]


def init_params(key, widths: Sequence[int]) -> Tuple[jax.Array, ...]:
    keys = jax.random.split(key, len(widths) - 1)
    return tuple(_haar_unitaries(keys[l - 1], widths[l], 2 ** (widths[l - 1] + 1))
                 for l in range(1, len(widths)))


@functools.partial(jax.jit, static_argnames=("widths", "num_nodes",
                                             "n_per_node", "n_test"))
def make_federation(key, *, widths, num_nodes, n_per_node, n_test
                    ) -> Federation:
    k_u, k_tr, k_te, k_p = jax.random.split(key, 4)
    d = 2 ** widths[0]
    with jax.default_matmul_precision("highest"):
        u = _haar_unitaries(k_u, 1, d)[0]
        phi_in = _haar_states(k_tr, num_nodes * n_per_node, d)
        order = jnp.argsort(jnp.angle(phi_in[:, 0]))
        phi_in = phi_in[order]
        phi_out = phi_in @ u.T
        test_in = _haar_states(k_te, n_test, d)
        test_out = test_in @ u.T
        params = init_params(k_p, widths)
    shape = (num_nodes, n_per_node, d)
    return Federation(phi_in.reshape(shape), phi_out.reshape(shape),
                      test_in, test_out, params)


@functools.partial(jax.jit, static_argnames=("widths",))
def make_params(key, *, widths):
    with jax.default_matmul_precision("highest"):
        return init_params(key, widths)
