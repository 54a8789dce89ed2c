"""A binomial GLM fit with no penalty: at the maximum-likelihood coefficients
the score equations hold, X'(y - sigmoid(X beta + b)) / n = 0 with the
intercept's column of ones among X. The reference evaluates them in float64
over ALL rows, column by column, at the coefficients the system returns, and
asks that their largest absolute entry stay under ``score_max_abs``.
"""

import numpy as np

from benchmark import refs


def score_equations(cols, features, response, coef, intercept):
    """Max-norm of the mean score, and the mean score itself."""
    n = len(cols[response])
    eta = np.full(n, float(intercept))
    for f, b in zip(features, coef):
        eta += float(b) * cols[f]               # float32 column into float64
    resid = cols[response] - refs.sigmoid(eta)
    score = np.array([resid @ cols[f] for f in features] + [resid.sum()]) / n
    return float(np.abs(score).max()), score


def check(state, model, tol):
    coef = model.coef
    features = state["features"]
    beta = [coef[f] for f in features]
    if not np.all(np.isfinite(beta + [coef["Intercept"]])):
        return False, {"coef": "not finite"}
    worst, _ = score_equations(state["cols"], features, state["response"],
                               beta, coef["Intercept"])
    return worst <= tol["score_max_abs"], {"score_max_abs": worst}
