"""Operations of one federation round, counted from shapes alone.

The count is the arithmetic of the exact ``local`` engine's round
(Prop. 1 on state-vector ensembles, Eq. 6 / Eq. 8 combine, polish) at
a configuration's widths and a cell's federation shape. It depends on
shapes only, never on what implements them, so a later change of
implementation moves the time and not the count.

Conventions, each a stated choice:

* A complex multiply-add is 8 real operations (4 multiplies, 4 adds).
* Applying a ``dk x dk`` perceptron unitary to a ``D``-dimensional
  vector costs ``D * dk`` complex multiply-adds (it acts on ``dk`` of
  the ``D`` amplitudes' tensor factors, ``D / dk`` times).
* Ensemble sizes are their rank bounds: the forward ensemble of layer
  ``l``'s input holds ``min(2**m_{l-1}, folded size)`` vectors (1 for the
  pure input); the label ensemble ``sigma^L`` holds 1 vector and every
  ``sigma^{l-1}`` at most ``2**m_{l-1}``; the B ensemble of layer ``l`` is
  ``2**m_{l-1}`` times the ``sigma^l`` ensemble.
* The commutator trace of one perceptron on ensembles of ``E_A`` and
  ``E_B`` vectors of dimension ``D``: the Gram (``E_A E_B D``), the fold
  of the larger ensemble onto the smaller (``E_A E_B D``), the partial
  trace over the rest (``min(E_A, E_B) dk D``).
* An ``n x n`` Hermitian eigendecomposition with eigenvectors is
  ``9 n**3`` floating-point operations as Golub and Van Loan count the
  symmetric QR algorithm (Matrix Computations, 4th ed., §8.3), each
  taken as a complex operation of 4 real ones: ``36 n**3``.
* ``exp(i s K) = V e^{i s lam} V†`` from cached factors: ``n**3``
  complex multiply-adds; one matrix product: ``n**3``.
* The polish ``U (3I - U†U) / 2``: two products.
"""
from __future__ import annotations

from typing import Sequence

CMAC = 8


def eigh_ops(n: int) -> float:
    return 36.0 * n ** 3


def _forward_ranks(widths: Sequence[int]):
    """Vectors in the ensemble of rho^{l-1}, per layer l = 1..L."""
    ranks, e = [], 1
    for l in range(1, len(widths)):
        ranks.append(e)
        e = min(2 ** widths[l], e * 2 ** widths[l - 1])
    return ranks


def _sigma_ranks(widths: Sequence[int]):
    """Vectors in the ensemble of sigma^l, per layer l = 1..L."""
    L = len(widths) - 1
    ranks = [0] * L
    r = 1
    for l in range(L, 0, -1):
        ranks[l - 1] = r
        r = min(2 ** widths[l - 1], 2 ** widths[l - 1] * r)
    return ranks


def node_step_ops(widths: Sequence[int], n_pairs: int) -> float:
    """One Prop.-1 step on one node's ``n_pairs`` pairs, the update of
    its local copy included."""
    cmac = 0.0
    ops = 0.0
    e_a = _forward_ranks(widths)
    r_s = _sigma_ranks(widths)
    for l in range(1, len(widths)):
        m_in, m_out = widths[l - 1], widths[l]
        d_in, dk, dim = 2 ** m_in, 2 ** (m_in + 1), 2 ** (m_in + m_out)
        ea, eb = e_a[l - 1], d_in * r_s[l - 1]
        per_pair = (ea * m_out * dim * dk                    # A chain
                    + eb * (m_out - 1) * dim * dk            # B chain peels
                    + r_s[l - 1] * d_in * d_in * 2 ** m_out  # sigma^{l-1}
                    + m_out * (2 * ea * eb * dim + min(ea, eb) * dk * dim))
        cmac += n_pairs * per_pair
        cmac += d_in * m_out * dim * dk                      # basis response
        cmac += m_out * 2 * dk ** 3                          # exp + apply
        ops += m_out * eigh_ops(dk)
    return ops + CMAC * cmac


def combine_ops(widths: Sequence[int], nodes_per_round: int,
                interval_length: int, aggregation: str) -> float:
    """The server's combine (Eq. 6 product from the node pass's
    eigenvectors, or Eq. 8 mean with its own eigendecomposition) and the
    polish of every perceptron."""
    cmac = 0.0
    ops = 0.0
    for l in range(1, len(widths)):
        m_out, dk = widths[l], 2 ** (widths[l - 1] + 1)
        if aggregation == "product":
            cmac += m_out * nodes_per_round * interval_length * 2 * dk ** 3
        elif aggregation == "average":
            cmac += m_out * interval_length * (nodes_per_round * dk ** 2
                                               + 2 * dk ** 3)
            ops += m_out * interval_length * eigh_ops(dk)
        else:
            raise ValueError(f"unknown aggregation {aggregation!r}")
        cmac += m_out * 2 * dk ** 3                          # polish
    return ops + CMAC * cmac


def round_ops(widths: Sequence[int], nodes_per_round: int,
              interval_length: int, n_pairs: int,
              aggregation: str) -> float:
    """Real operations of one federation round."""
    return (nodes_per_round * interval_length * node_step_ops(widths, n_pairs)
            + combine_ops(widths, nodes_per_round, interval_length,
                          aggregation))
