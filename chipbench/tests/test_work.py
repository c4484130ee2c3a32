"""work.py's count follows its formulas; peaks.json refuses an unknown
device kind."""
import pytest

from chipbench import harness, work


def test_eigh_convention():
    assert work.eigh_ops(8) == 36 * 8 ** 3


def test_count_scales_with_the_federation_shape():
    w = (2, 3, 2)
    one = work.node_step_ops(w, 4)
    # the node pass is linear in nodes and in local steps; the combine
    # of the product rule is linear in both too
    r1 = work.round_ops(w, 10, 2, 4, "product")
    r2 = work.round_ops(w, 20, 2, 4, "product")
    assert r2 - r1 == pytest.approx(10 * 2 * one
                                    + work.combine_ops(w, 20, 2, "product")
                                    - work.combine_ops(w, 10, 2, "product"))
    assert work.combine_ops(w, 20, 2, "product") - work.combine_ops(
        w, 10, 2, "product") == pytest.approx(
            work.CMAC * 10 * 2 * 2 * (3 * 8 ** 3 + 2 * 16 ** 3))
    # per-pair work is linear in the pairs; the rest is fixed per step
    fixed = work.node_step_ops(w, 0)
    assert work.node_step_ops(w, 8) - fixed == pytest.approx(
        2 * (work.node_step_ops(w, 4) - fixed))


def test_count_scales_with_width():
    # a perceptron of a wider layer costs more; the eigh term alone grows
    # as dk**3 per perceptron
    small = work.round_ops((2, 3, 2), 10, 2, 4, "product")
    wide = work.round_ops((4, 5, 4), 10, 2, 4, "product")
    assert wide > 50 * small
    avg = work.combine_ops((2, 3, 2), 10, 2, "average")
    assert avg - work.CMAC * (3 * 2 * (10 * 64 + 2 * 512) + 2 * 2 * (10 * 256 + 2 * 4096)
                              + 3 * 2 * 512 + 2 * 2 * 4096) == pytest.approx(
        3 * 2 * work.eigh_ops(8) + 2 * 2 * work.eigh_ops(16))


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
