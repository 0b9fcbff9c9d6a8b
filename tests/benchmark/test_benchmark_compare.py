"""The comparison that decides ``correct``, fed sound and broken readings."""

import math

import numpy as np
import pytest

from benchmark import compare

LIMITS = {"loss_rel_gap": 0.01, "grad1_norm_worst_leaf_gap": 0.05,
          "change_norm_worst_leaf_gap": 0.1,
          "grad1_norm_median_leaf_gap": 0.02,
          "change_norm_median_leaf_gap": 0.04}


def _ref():
    grads = {f"l{i}/W": 1.0 + 0.1 * i for i in range(9)}
    grads["dead/b"] = 1e-9             # nought to rounding in the reference
    change = {k: 0.05 * v for k, v in grads.items()}
    return {"losses": [7.9, 7.7, 7.5], "grad1_norms": grads,
            "change_norms": change}


def _scaled(ref, loss=1.0, grad=1.0, change=1.0):
    return {"losses": [v * loss for v in ref["losses"]],
            "grad1_norms": {k: v * grad
                            for k, v in ref["grad1_norms"].items()},
            "change_norms": {k: v * change
                             for k, v in ref["change_norms"].items()}}


def _verdict(prog):
    checks = compare.training_checks(prog, _ref(), LIMITS)
    return all(c.ok for c in checks), {c.name: c for c in checks}


def test_sound_readings_pass_every_number():
    ok, checks = _verdict(_scaled(_ref(), 1.001, 1.01, 0.99))
    assert ok
    assert set(checks) == {"loss_step1_rel_gap", "loss_step2_rel_gap",
                           "loss_step3_rel_gap",
                           "grad1_norm_worst_leaf_gap",
                           "change_norm_worst_leaf_gap",
                           "grad1_norm_median_leaf_gap",
                           "change_norm_median_leaf_gap"}


@pytest.mark.parametrize("fault,prog,failing", [
    ("a step that returns its state unchanged",
     _scaled(_ref(), change=0.0), "change_norm_worst_leaf_gap"),
    ("a leaf moved double", _scaled(_ref(), change=2.0),
     "change_norm_worst_leaf_gap"),
    ("half the batch left out", _scaled(_ref(), loss=1.02, grad=1.4),
     "grad1_norm_worst_leaf_gap"),
    ("a lower precision", _scaled(_ref(), loss=1.03), "loss_step1_rel_gap"),
    ("a loss that is not a number", _scaled(_ref(), loss=math.nan),
     "loss_step1_rel_gap"),
])
def test_each_fault_fails_a_number_of_its_own(fault, prog, failing):
    ok, checks = _verdict(prog)
    assert not ok, fault
    assert not checks[failing].ok


def test_a_state_left_unchanged_reads_one_by_the_measure():
    ref = _ref()
    gap = compare.worst_leaf_gap({k: 0.0 for k in ref["change_norms"]},
                                 ref["change_norms"],
                                 compare.moved_leaves(ref["grad1_norms"]))
    assert gap == pytest.approx(1.0)


def test_leaves_with_no_gradient_are_left_out_by_rule_not_by_name():
    ref = _ref()
    moved = compare.moved_leaves(ref["grad1_norms"])
    assert "dead/b" not in moved and len(moved) == 9
    # the dead leaf moves by round-off alone in the program: not compared
    prog = _scaled(ref)
    prog["change_norms"]["dead/b"] = 1e-3
    ok, _ = _verdict(prog)
    assert ok
    # but a small live leaf is held against the median leaf, not itself
    prog = _scaled(ref)
    small = dict(ref["change_norms"], **{"l0/W": 1e-6})
    prog_small = dict(small, **{"l0/W": 3e-6})
    assert compare.worst_leaf_gap(prog_small, small) < 1e-4


def test_the_median_leaf_holds_still_where_one_small_leaf_is_noise():
    """One leaf off by 30% fails the worst leaf and leaves the median
    where it was; every leaf off by 3% moves the median."""
    ref = _ref()
    one = _scaled(ref, grad=1.0)
    one["grad1_norms"]["l0/W"] *= 1.3
    ok, checks = _verdict(one)
    assert not ok and not checks["grad1_norm_worst_leaf_gap"].ok
    assert checks["grad1_norm_median_leaf_gap"].value == 0.0
    ok, checks = _verdict(_scaled(ref, grad=1.03))
    assert not ok and checks["grad1_norm_worst_leaf_gap"].ok
    # (leaves under the median are held against the median leaf's norm)
    assert 0.025 < checks["grad1_norm_median_leaf_gap"].value <= 0.03
    assert not checks["grad1_norm_median_leaf_gap"].ok
    prog = dict(ref["grad1_norms"])
    prog.pop("l3/W")
    assert compare.median_leaf_gap(prog, ref["grad1_norms"]) == math.inf


def test_a_missing_leaf_is_infinitely_wrong():
    ref = _ref()
    prog = dict(ref["grad1_norms"])
    prog.pop("l3/W")
    assert compare.worst_leaf_gap(prog, ref["grad1_norms"]) == math.inf


def test_widest_token_gap():
    logits = np.array([[0.0, 1.0, 3.0], [2.0, 1.9, -1.0], [0.5, 0.5, 0.4]])
    assert compare.widest_token_gap(logits, [2, 0, 1]) == 0.0
    assert compare.widest_token_gap(logits, [2, 1, 0]) == \
        pytest.approx(0.1)
    # a token altered where it is produced lies far below the best
    assert compare.widest_token_gap(logits, [0, 0, 0]) == pytest.approx(3.0)


@pytest.mark.parametrize("value,limit,exact,ok", [
    (0.01, 0.02, False, True), (0.03, 0.02, False, False),
    (math.nan, 0.02, False, False), (math.inf, 0.02, False, False),
    (0, 0, True, True), (1, 0, True, False),
])
def test_check_semantics(value, limit, exact, ok):
    c = compare.Check("x", value, limit, exact=exact)
    assert c.ok is ok
    assert ("ok" if ok else "FAILED") in c.line()
    assert c.as_dict()["limit"] == limit
