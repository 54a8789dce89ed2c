"""The ``op_share`` reduction on the recorded v5e trace.

``data/predict_10m.xplane.pb`` is test_reduce.py's trace (one ``predict``
of a 100-tree model over 10M rows); the numbers were read from it by hand:
its window ``bench:predict`` is 3933.79 ms, ``%fusion.113`` runs 100 times
inside a ``%while`` for 338.414 ms in all, and the three
``%multiply_add_fusion.{6,7,8}`` for 422.92 ms each."""

import os

import pytest

from benchmark import reduce as R
from benchmark.reductions import op_share

TRACE = os.path.join(os.path.dirname(__file__), "data", "predict_10m.xplane.pb")


@pytest.fixture(scope="module")
def ctx():
    trace = R.Trace.from_file(TRACE)
    window = trace.window("bench:predict")
    return {"trace": trace, "window": window,
            "window_s": (window[1] - window[0]) / 1e9}


@pytest.mark.parametrize("match,percent", [
    (r"^%fusion\.113 ", 100 * 0.338414 / 3.933789),
    (r"^%multiply_add_fusion\.", 100 * 3 * 0.42292 / 3.933789),
])
def test_share_of_the_ops_matched_by_name(ctx, match, percent):
    assert op_share.reduce({"kind": "op_share", "match": match}, ctx) \
        == pytest.approx(percent, rel=1e-4)


def test_an_enclosing_while_is_not_counted(ctx):
    """Every op of the traversal runs inside ``%while``; counted as a leaf
    it would read ~74 % of the window."""
    assert op_share.reduce({"kind": "op_share", "match": r"^%while"}, ctx) is None


def test_nothing_to_read_gives_none(ctx):
    assert op_share.reduce({"kind": "op_share", "match": r"^%hist_"}, ctx) is None
    no_device = dict(ctx, trace=R.Trace({}, []))
    assert op_share.reduce({"kind": "op_share", "match": r"."}, no_device) is None
