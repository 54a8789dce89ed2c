"""Plain references: numpy, float64, no code of the system under test.

Each module has ``check(state, result, tol) -> (ok, detail)``: ``state`` is
the driver's (host columns from the seed among it), ``result`` what the last
unit of work returned, ``tol`` the tolerances of the cell's file under
``checks/``, each written there with its reason.
"""

import numpy as np


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


def auc(score, label):
    """Area under the ROC curve by ranks (ties get their mean rank)."""
    score, label = np.asarray(score, np.float64), np.asarray(label).astype(bool)
    order = np.argsort(score, kind="stable")
    s = score[order]
    first = np.r_[True, s[1:] != s[:-1]]
    start = np.flatnonzero(first)
    end = np.r_[start[1:], len(s)]
    mean_rank = (start + end + 1) / 2.0           # ranks are 1-based
    ranks = np.empty(len(s))
    ranks[order] = np.repeat(mean_rank, end - start)
    pos = int(label.sum())
    neg = len(label) - pos
    return (ranks[label].sum() - pos * (pos + 1) / 2.0) / (pos * neg)


def design(cols, features):
    """Raw-value matrix as a tree model sees it: numerics as they are,
    categoricals as their codes."""
    return np.stack([np.asarray(cols[f], np.float64) for f in features], axis=1)


def walk_trees(levels, values, X):
    """Sum of leaf values over the trees for the rows of X.

    ``levels[d]`` holds, for every tree t and node k of depth d, the split
    feature, the threshold, whether NA goes left, and whether the node
    splits at all; ``values[t]`` the leaves. A row goes right where its value
    is at least the threshold (NA: where NA does not go left) and the node is
    valid, else left."""
    values = np.asarray(values, np.float64)
    total = np.zeros(len(X))
    rows = np.arange(len(X))
    for t in range(values.shape[0]):
        node = np.zeros(len(X), np.int64)
        for feat, thr, na_left, valid in levels:
            f = np.asarray(feat)[t][node]
            x = X[rows, f]
            right = np.where(np.isnan(x), ~np.asarray(na_left)[t][node],
                             x >= np.asarray(thr, np.float64)[t][node])
            node = 2 * node + (right & np.asarray(valid)[t][node])
        total += values[t][node]
    return total
