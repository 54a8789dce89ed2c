"""A boosted-tree fit for a binary label: the root split of the first tree,
and the training AUC.

Root split. At the start every row has the same prediction p0 (the model's
initial score through the logistic link), so the gradient is p0 - y and the
hessian p0 (1 - p0). The reference sums both per candidate threshold for
every feature over ALL rows (numerics: 255 quantile cuts of a 1M-row sample;
categoricals: every code boundary) and takes XGBoost's gain
GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda). It then computes the
same gain for the split the system chose, from the raw values, and asks that
it reach ``root_gain_ratio_min`` of the reference's best. Equality of the
arg-max is not asked: near-ties flip under the system's bf16 histogram.

AUC. ``predict`` of the model on a seeded sample of rows, ranked in numpy
against the labels, must lie inside ``auc_band``.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import refs


def _gain(GL, HL, G, H, lam):
    GR, HR = G - GL, H - HL
    return GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam) - G ** 2 / (H + lam)


def best_root_gain(cols, features, categorical, g, h, lam, seed):
    """(best gain, feature, threshold) over the reference's own candidates."""
    n = len(g)
    G, H = float(g.sum()), h * n
    sample = np.random.default_rng(seed).integers(0, n, min(n, 1_000_000))

    def one(f):
        x = cols[f]
        if f in categorical:
            cuts = np.arange(1, int(x.max()) + 1) - 0.5
            bins = np.asarray(x, np.int64)
        else:
            cuts = np.unique(np.quantile(x[sample], np.linspace(0, 1, 257)[1:-1]))
            bins = np.searchsorted(cuts.astype(x.dtype), x, side="right")
        GL = np.cumsum(np.bincount(bins, weights=g, minlength=len(cuts) + 1))[:-1]
        HL = h * np.cumsum(np.bincount(bins, minlength=len(cuts) + 1))[:-1]
        gains = _gain(GL, HL, G, H, lam)
        k = int(np.argmax(gains))
        return float(gains[k]), f, float(cuts[k])

    with ThreadPoolExecutor(len(features)) as pool:
        return max(pool.map(one, features))


def split_gain(x, thr, g, h, lam):
    left = x < thr
    return float(_gain(g[left].sum(), h * left.sum(), g.sum(), h * len(g), lam))


def check(state, model, tol):
    cols, features = state["cols"], state["features"]
    y = cols[state["response"]]
    stacked = model.output["stacked"]
    feat, thr, _, valid = (np.asarray(a)[0, 0] for a in stacked.levels[0])
    if not valid:
        return False, {"root": "the first tree has no split"}
    p0 = float(refs.sigmoid(model.output["init_score"]))
    g, h, lam = p0 - y.astype(np.float64), p0 * (1 - p0), float(model.params.reg_lambda)
    best, best_f, best_thr = best_root_gain(
        cols, features, state["categorical"], g, h, lam, state["seed"])
    chosen_f = features[int(feat)]
    chosen = split_gain(cols[chosen_f], float(thr), g, h, lam)
    detail = {"root_chosen": [chosen_f, float(thr), chosen],
              "root_reference": [best_f, best_thr, best],
              "root_gain_ratio": chosen / best}
    ok = chosen >= tol["root_gain_ratio_min"] * best

    rows = np.random.default_rng(state["seed"]).choice(
        len(y), min(len(y), tol["auc_sample_rows"]), replace=False)
    rows.sort()
    sample = state["make_frame"]({k: v[rows] for k, v in cols.items()})
    p1 = sample_p1(model, sample, state)
    detail["auc"] = refs.auc(p1, y[rows])
    lo, hi = tol["auc_band"]
    return bool(ok and lo <= detail["auc"] <= hi), detail


def sample_p1(model, frame, state):
    """The model's probability of the label's second level, on the host."""
    positive = state["domains"][state["response"]][1]
    return np.asarray(model.predict(frame).vec(positive).to_numpy(), np.float64)
