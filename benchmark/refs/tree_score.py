"""Scoring a frame through a boosted-tree model for a binary label: on a
seeded sample of rows, ``predict`` must equal a numpy walk of the model's
stacked arrays (initial score plus the leaves reached, through the logistic
link) to ``p1_max_abs``. The rows are taken from the output of the window's
last call, so what is checked is what was timed.
"""

import numpy as np

from benchmark import refs


def check(state, predictions, tol):
    model, cols = state["model"], state["cols"]
    n = len(cols[state["response"]])
    positive = state["domains"][state["response"]][1]
    got_all = predictions.vec(positive)
    if predictions.nrows != n:
        return False, {"rows": predictions.nrows}
    rows = np.random.default_rng(state["seed"]).choice(
        n, min(n, tol["sample_rows"]), replace=False)
    rows.sort()
    stacked = model.output["stacked"]
    levels = [tuple(np.asarray(a) for a in lv) for lv in stacked.levels]
    margin = model.output["init_score"] + refs.walk_trees(
        levels, np.asarray(stacked.values),
        refs.design({f: cols[f][rows] for f in state["features"]}, state["features"]))
    want = refs.sigmoid(margin)
    got = np.asarray(got_all.to_numpy(), np.float64)[rows]
    worst = float(np.abs(got - want).max())
    return worst <= tol["p1_max_abs"], {"p1_max_abs": worst, "rows": len(rows)}
