"""Plain reference of GLM's IRLSM: the working weights and response, the
Gram, the penalized solve and the forward pass, in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``, on
the DENSE one-hot expansion of the frame (``DataInfo.make_matrix``).  No row
blocks, no code form, no ``while_loop``, no code of ``glm.py``: the tests
hold the system against this (``tests/test_glm_coded.py``), and
``benchmark/refs/glm_fit_coded.py`` is its float64 numpy twin for the chip.

With ``X`` the expanded rows [n, P] (the intercept's column of ones among
them, last), ``eta = X beta + offset``, ``mu = linkinv(eta)``, ``g = d mu /
d eta`` and ``var`` the family's variance function, one IRLS pass at
``beta`` is

    W = w g^2 / var,    z = (eta - offset) + (y - mu) / g
    Gram = X' W X,      X'Wz,      deviance(y, mu, w)

and the update solves, with N the summed row weights,

    (Gram / N + lambda (1 - alpha) D) beta' = X'Wz / N        (no L1)
    min_b  b' Gram b / 2N - b' X'Wz / N
           + lambda sum_j D_jj (alpha |b_j| + (1 - alpha) b_j^2 / 2)

the second by cyclic coordinate descent on the Gram (soft thresholding),
until no coefficient moves by ``beta_epsilon`` or more.  D is 1 on every
coefficient but the intercept's, which is never penalized.

Departures from H2O-3 (``hex/glm``), which the system shares: a categorical
drops its first level (``use_all_factor_levels=False``) and gains one column
for NA and unseen levels, where H2O-3 imputes a missing categorical with its
mode; numerics are mean-imputed and standardised by the frame's rollups;
1e-10 is added to the diagonal of every solve, so that a column no row
lights (an NA column of a frame without NAs) keeps the coefficient 0;
``lambda_`` has no default search: left unset it is 0, where H2O-3 picks a
lambda from the data; the start is beta = 0 with the intercept at the link
of the weighted mean response.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _linkinv(family: str, eta):
    if family == "gaussian":
        return eta
    if family == "binomial":
        return 1.0 / (1.0 + jnp.exp(-eta))
    if family == "poisson":
        return jnp.exp(jnp.clip(eta, -30.0, 30.0))
    raise ValueError(f"the reference has no family {family!r}")


def _variance(family: str, mu):
    if family == "gaussian":
        return jnp.ones_like(mu)
    if family == "binomial":
        return mu * (1.0 - mu)
    return mu                                           # poisson


def _deviance(family: str, y, mu, w):
    if family == "gaussian":
        return jnp.sum(w * (y - mu) ** 2)
    if family == "binomial":
        mu = jnp.clip(mu, 1e-15, 1.0 - 1e-15)
        return -2.0 * jnp.sum(w * (y * jnp.log(mu) + (1.0 - y) * jnp.log1p(-mu)))
    mu = jnp.maximum(mu, 1e-15)
    return 2.0 * jnp.sum(w * (jnp.where(y > 0, y * jnp.log(y / mu), 0.0) - (y - mu)))


def _link_of_mean(family: str, y, w) -> float:
    mean = float(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12))
    if family == "gaussian":
        return mean
    if family == "binomial":
        p = min(max(mean, 1e-6), 1.0 - 1e-6)
        return float(np.log(p / (1.0 - p)))
    return float(np.log(max(mean, 1e-6)))


def irls_stats(X, y, w, beta, offset, family: str):
    """(Gram [P, P], X'Wz [P], deviance) of one IRLS pass at ``beta``.  The
    canonical links only: g = var (clamped at 1e-10 as the system clamps
    both)."""
    with jax.default_matmul_precision("highest"):
        X, y, w = (jnp.asarray(a, jnp.float32) for a in (X, y, w))
        eta = X @ jnp.asarray(beta, jnp.float32) + offset
        mu = _linkinv(family, eta)
        g = jnp.maximum(_variance(family, mu), 1e-10)
        z = (eta - offset) + (y - mu) / g
        XW = X * (w * g)[:, None]                   # w g^2 / var, g = var
        return XW.T @ X, XW.T @ z, _deviance(family, y, mu, w)


def _coordinate_descent(G, c, l1, l2, penalized, beta, sweeps=100, tol=1e-8):
    """argmin b'Gb/2 - c'b + sum_j l1_j |b_j| + l2_j b_j^2 / 2, cyclic, from
    ``beta``: float32 numpy scalars, one coefficient at a time."""
    G, c, beta = (np.asarray(a, np.float32) for a in (G, c, beta))
    beta = beta.copy()
    for _ in range(sweeps):
        moved = np.float32(0.0)
        for j in range(len(beta)):
            r = c[j] - (G[j] @ beta - G[j, j] * beta[j])
            if penalized[j]:
                new = np.sign(r) * max(abs(r) - l1[j], np.float32(0.0)) \
                    / (G[j, j] + l2[j] + np.float32(1e-12))
            else:
                new = r / (G[j, j] + np.float32(1e-12))
            moved = max(moved, abs(new - beta[j]))
            beta[j] = new
        if moved <= tol:
            break
    return beta


def fit(X, y, w, offset, family: str, lambdas: Sequence[float],
        alpha: float = 0.5, intercept: bool = True, max_iterations: int = 50,
        beta_epsilon: float = 1e-5) -> Tuple[np.ndarray, np.ndarray, list]:
    """The lambda path, each lambda warm-started from the one before:
    (coefficients [lambdas, P], the deviance each lambda's last pass read,
    the IRLS passes each took)."""
    X, y, w = (jnp.asarray(a, jnp.float32) for a in (X, y, w))
    offset = jnp.zeros_like(y) if offset is None else jnp.asarray(offset, jnp.float32)
    n_coef = X.shape[1]
    n = float(jnp.sum(w))
    penalized = np.ones(n_coef, bool)
    beta = np.zeros(n_coef, np.float32)
    if intercept:
        penalized[-1] = False
        beta[-1] = _link_of_mean(family, y, w)
    betas, devs, passes = [], [], []
    for lam in lambdas:
        l1 = (lam * alpha * penalized).astype(np.float32)
        l2 = (lam * (1.0 - alpha) * penalized).astype(np.float32)
        for it in range(max_iterations):
            gram, xtwz, dev = irls_stats(X, y, w, beta, offset, family)
            G, c = gram / n, xtwz / n
            if alpha > 0 and lam > 0:
                new = _coordinate_descent(G, c, l1, l2, penalized, beta)
            else:
                with jax.default_matmul_precision("highest"):
                    new = np.asarray(jnp.linalg.solve(
                        G + jnp.diag(jnp.asarray(l2 + 1e-10, jnp.float32)), c))
            moved = float(np.max(np.abs(new - beta)))
            beta = np.asarray(new, np.float32)
            if moved < beta_epsilon:
                break
        betas.append(beta.copy())
        devs.append(float(dev))
        passes.append(it + 1)
    return np.stack(betas), np.asarray(devs), passes


def predict(X, beta, family: str):
    """The mean response [n] of the dense rows ``X`` (for a binomial model
    the probability of the second class)."""
    with jax.default_matmul_precision("highest"):
        return _linkinv(family, jnp.asarray(X, jnp.float32)
                        @ jnp.asarray(beta, jnp.float32))
