"""The numbers that decide ``correct``, on hand-made readings."""
import math

import pytest

from harness import compare

PATHS = ["a", "b", "c"]


def _reading(dnorm, margins=(math.inf,) * 3, change=(1.0, 2.0, 3.0)):
    return {"paths": PATHS, "loss": [2.0, 2.0, 2.0],
            "direction_norm": list(dnorm), "nnm_margin": list(margins),
            "grad_norms": [[1.0, 2.0, 3.0]] * 2, "change_norms": list(change)}


@pytest.mark.parametrize("margin, gap", [(1e-2, 0.1), (1e-4, 0.0)],
                         ids=["decided", "near_tie"])
def test_a_near_tie_nnm_step_leaves_dnorm_gap(margin, gap):
    ref = _reading([1.0, 1.0, 1.0], margins=(math.inf, margin, math.inf))
    prog = _reading([1.0, 1.1, 1.0])
    assert compare.numbers(prog, ref)["dnorm_gap"] == pytest.approx(gap)


def test_leaf_gap_is_over_the_larger_of_leaf_and_median():
    ref = _reading([1.0] * 3, change=(0.01, 2.0, 3.0))
    prog = _reading([1.0] * 3, change=(0.0, 2.0, 3.3))
    # Leaf a: 0.01 over the median 2.0; leaf c: 0.3 over its own 3.0.
    assert compare.numbers(prog, ref)["change_gap"] == pytest.approx(0.1)
    assert compare.leaf_report(prog, ref, k=1) == [["c", 3.3, 3.0,
                                                    pytest.approx(0.1)]]
