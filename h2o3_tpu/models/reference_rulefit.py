"""Plain reference of RuleFit's rule design and lasso path, in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: no codes, no kernels, no row
blocks, no penalty factors and no code of ``rulefit.py``.  The tests hold
the system against it (``tests/test_rulefit_coded.py``), and
``benchmark/refs/rulefit_fit.py`` is its float64 numpy twin for the chip.

From a forest's split tables (per depth d: feature, threshold, NA-left and
split-or-not [trees, 2^d], as ``StackedTrees.levels`` holds them) every row
is walked down every tree: right where its feature is at least the
threshold, NaN where NA does not go left, and only at a node that splits.
The rule of node k at depth d of tree t is the 0/1 column "the row is at
that node", so the dense rule matrix R [rows, rules] has one column per
rule listed.  With the linear numerics Z beside it, every column is
standardised by its own mean and sample deviation over the values it has
(a deviation of 0 taken as 1, a NaN imputed with the mean), the intercept's column
of ones last, and the path solves, for each lambda from the largest down,

    min_b  -(1/N) sum_i w_i loglik(y_i, X_i b) + lambda sum_{j not intercept} |b_j|

by IRLS with cyclic coordinate descent on each pass's Gram
(``reference_glm.fit`` at ``alpha=1``), warm-started along the path.

Departures from H2O-3's RuleFit (``hex/rulefit``), which the system shares:
rule generation is this package's DRF or GBM, whose trees are grown on
binned columns; ONE forest of depth ``max_rule_length`` gives the rules of
every length (its nodes at the depths asked), where H2O-3 grows a forest
for each rule length (written from memory); a row with a missing value follows the side its node
sends NA to, as the generator's trees score it; rules with equal
condition lists (in the path's order) are dropped after the first
(``remove_duplicates``); the lambda
path has 30 lambdas down to 1e-4 of the largest and the model keeps the
last, where H2O-3 searches 100 with early stopping; the linear terms are
not winsorized; multinomial responses are refused.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_glm


def node_ids(levels, X) -> jnp.ndarray:
    """[trees, depth + 1, rows]: every row's node id at every depth (0 at
    the root) of every tree, walking the rows of ``X`` [rows, features]."""
    X = jnp.asarray(X, jnp.float32)
    trees, rows = np.asarray(levels[0][0]).shape[0], X.shape[0]
    out = []
    for t in range(trees):
        node = jnp.zeros(rows, jnp.int32)
        path = [node]
        for feat, thr, na_left, valid in levels:
            f = jnp.asarray(np.asarray(feat)[t])[node]
            x = X[jnp.arange(rows), f]
            right = jnp.where(jnp.isnan(x), ~jnp.asarray(np.asarray(na_left)[t])[node],
                              x >= jnp.asarray(np.asarray(thr)[t])[node])
            right = right & jnp.asarray(np.asarray(valid)[t])[node]
            node = 2 * node + right.astype(jnp.int32)
            path.append(node)
        out.append(jnp.stack(path))
    return jnp.stack(out)


def rule_matrix(levels, X, rules: Sequence[Tuple[int, int, int]]) -> jnp.ndarray:
    """[rows, rules] float32: 1 where the row is at the rule's node."""
    ids = node_ids(levels, X)
    if not rules:
        return jnp.zeros((X.shape[0], 0), jnp.float32)
    return jnp.stack([(ids[t, d] == k).astype(jnp.float32)
                      for t, d, k in rules], axis=1)


def standardised(columns) -> Tuple[jnp.ndarray, np.ndarray, np.ndarray]:
    """(columns standardised, their means, their deviations): each
    column's mean and sample deviation (ddof 1) over its values that are
    not NaN, a deviation of 0 taken as 1; NaN imputed with the mean, then
    each column less its mean over its deviation."""
    with jax.default_matmul_precision("highest"):
        C = jnp.asarray(columns, jnp.float32)
        mean = jnp.nanmean(C, axis=0)
        sd = jnp.nanstd(C, axis=0, ddof=1)
        sd = jnp.where(sd > 0, sd, 1.0)
        C = jnp.where(jnp.isnan(C), mean, C)
        return (C - mean) / sd, np.asarray(mean), np.asarray(sd)


def design(R, Z=None) -> jnp.ndarray:
    """The standardised design [rules, linear, intercept]."""
    parts = [R] if Z is None else [R, Z]
    X, _, _ = standardised(jnp.concatenate(parts, axis=1))
    return jnp.concatenate([X, jnp.ones((X.shape[0], 1), jnp.float32)], axis=1)


def objective(X, y, w, beta, lam: float, family: str = "binomial") -> float:
    """The penalized objective, deviance / 2N + lambda |b| (the intercept,
    last, unpenalized)."""
    with jax.default_matmul_precision("highest"):
        mu = reference_glm.predict(X, beta, family)
        dev = reference_glm._deviance(family, jnp.asarray(y, jnp.float32),
                                      mu, jnp.asarray(w, jnp.float32))
        return float(dev / (2 * float(np.sum(w)))
                     + lam * jnp.sum(jnp.abs(jnp.asarray(beta)[:-1])))


def lambda_max(X, y, w, family: str = "binomial") -> float:
    """The least lambda at which every penalized coefficient is 0."""
    with jax.default_matmul_precision("highest"):
        y, w = jnp.asarray(y, jnp.float32), jnp.asarray(w, jnp.float32)
        mu = reference_glm._linkinv(family, reference_glm._link_of_mean(
            family, y, w))
        return float(jnp.max(jnp.abs(jnp.asarray(X)[:, :-1].T @ (w * (y - mu))))
                     / jnp.sum(w))


def lasso_path(X, y, w, lambdas: Sequence[float], family: str = "binomial"):
    """Coefficients [lambdas, P] of the standardised design along the
    path, each warm-started from the one before."""
    betas, _, _ = reference_glm.fit(X, y, w, None, family, lambdas, alpha=1.0)
    return betas
