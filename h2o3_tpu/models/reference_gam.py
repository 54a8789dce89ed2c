"""Plain reference of a GAM of cubic regression splines (``bs="cr"``): the
knots, the cardinal natural cubic spline basis, its curvature penalty, the
sum-to-zero centring and the penalized IRLS, in float64 numpy from the
definitions alone, with no code of ``gam.py``, ``glm.py`` or ``datainfo.py``.
The tests hold the system against it (``tests/test_gam_coded.py``), and
``benchmark/refs/gam_fit.py`` is its twin for the chip, over row blocks.

A smooth of column x with K knots is the natural cubic spline f through
its values ``v`` [K] at the knots: on [k_j, k_j+1], with h = k_j+1 - k_j,

    f(x) = v_j (k_j+1 - x)/h + v_j+1 (x - k_j)/h
           + m_j ((k_j+1 - x)^3/h - h (k_j+1 - x)) / 6
           + m_j+1 ((x - k_j)^3/h - h (x - k_j)) / 6

where ``m = F v`` are its second derivatives at the knots (0 at both ends,
the rest from the continuity of f', a tridiagonal system), and x is clipped
to [k_0, k_K-1] (the spline goes on as a constant past its ends, as the
system's basis does).  ``cardinal(x, knots)`` [n, K] is f's row map: f(x) =
cardinal(x) v.  The curvature penalty is the integral of f''^2, which is
linear in m on each interval: ``v' S v`` with S = F' Q F and Q the
integrals of the interval hat functions' products (h/3 on the diagonal,
h/6 beside it).

A fit minimises

    (1/N) sum_i w_i deviance_i / 2 + sum_s lambda_s v_s' S_s v_s / 2

over the intercept, the linear terms' coefficients (unpenalized) and each
smooth's v_s under the centring ``c_s' v_s = 0``, c_s the mean cardinal row
over the frame's rows (so that f_s sums to 0 over them and the intercept
is the mean), with lambda_s = scale_s / the largest eigenvalue of S_s on
the centred subspace, and N the summed row weights.  It writes v_s = Z_s g_s
with Z_s an orthonormal basis of that subspace and runs Newton's method on
g to a step of ``tol``.

Departures from H2O-3 (``hex/gam``), which the system shares: knots are
the quantiles ``linspace(0, 1, K)`` of the column's non-missing values
with duplicates dropped, where H2O-3 places them by its own quantile task
(written from memory); a missing value is imputed by the column's mean;
the penalty's scale is normalised by its largest centred eigenvalue, where
H2O-3 scales penalty matrices by its own norm; the centring is over the
frame's rows, unweighted; the linear terms are not penalized (the fits
the tests make run at ``lambda_=0``); binomial and gaussian families only.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def knots(x: np.ndarray, k: int) -> np.ndarray:
    """The knots of a column: quantiles of its non-missing values at
    ``linspace(0, 1, k)`` (linear interpolation), duplicates dropped."""
    x = np.asarray(x, np.float64)
    return np.unique(np.quantile(x[~np.isnan(x)], np.linspace(0, 1, k)))


def second_derivatives(knots: np.ndarray) -> np.ndarray:
    """F [K, K]: the natural interpolating spline's second derivatives at
    the knots from its values there (first and last row 0)."""
    K = len(knots)
    h = np.diff(knots)
    A = np.zeros((K, K))
    R = np.zeros((K, K))
    A[0, 0] = A[-1, -1] = 1.0                       # m_0 = m_K-1 = 0
    for i in range(1, K - 1):
        A[i, i - 1] = h[i - 1] / 6.0
        A[i, i] = (h[i - 1] + h[i]) / 3.0
        A[i, i + 1] = h[i] / 6.0
        R[i, i - 1] = 1.0 / h[i - 1]
        R[i, i] = -1.0 / h[i - 1] - 1.0 / h[i]
        R[i, i + 1] = 1.0 / h[i]
    return np.linalg.solve(A, R)


def penalty(knots: np.ndarray) -> np.ndarray:
    """S [K, K]: v' S v is the integral of f''^2 over the knots' range."""
    h = np.diff(knots)
    Q = np.zeros((len(knots), len(knots)))
    for j, hj in enumerate(h):
        Q[j, j] += hj / 3.0
        Q[j + 1, j + 1] += hj / 3.0
        Q[j, j + 1] += hj / 6.0
        Q[j + 1, j] += hj / 6.0
    F = second_derivatives(knots)
    return F.T @ Q @ F


def cardinal(x: np.ndarray, knots: np.ndarray) -> np.ndarray:
    """[n, K]: row r gives f(x_r) = cardinal(x)[r] @ v for every v."""
    x = np.clip(np.asarray(x, np.float64), knots[0], knots[-1])
    K = len(knots)
    j = np.minimum(np.searchsorted(knots, x, side="right") - 1, K - 2)
    lo, hi = knots[j], knots[j + 1]
    h = hi - lo
    rows = np.arange(len(x))
    V = np.zeros((len(x), K))                      # the values' weights
    V[rows, j] += (hi - x) / h
    V[rows, j + 1] += (x - lo) / h
    M = np.zeros((len(x), K))                      # the second derivatives'
    M[rows, j] += ((hi - x) ** 3 / h - h * (hi - x)) / 6.0
    M[rows, j + 1] += ((x - lo) ** 3 / h - h * (x - lo)) / 6.0
    return V + M @ second_derivatives(knots)


class Smooth:
    """One smooth's pieces over the frame's column ``x``: knots, penalty,
    centring, its centred basis and its penalty weight."""

    def __init__(self, x: np.ndarray, k: int, scale: float):
        x = np.asarray(x, np.float64)
        self.mean = float(np.nanmean(x))
        self.x = np.where(np.isnan(x), self.mean, x)
        self.knots = knots(x, k)
        self.S = penalty(self.knots)
        B = cardinal(self.x, self.knots)
        self.c = B.mean(axis=0)
        # an orthonormal basis of {v : c'v = 0}
        _, _, vt = np.linalg.svd(self.c[None, :])
        self.Z = vt[1:].T
        self.lam = scale / np.linalg.eigvalsh(self.Z.T @ self.S @ self.Z).max()
        self.B = B @ self.Z


def _mu(family: str, eta: np.ndarray) -> np.ndarray:
    if family == "binomial":
        return 1.0 / (1.0 + np.exp(-eta))
    if family == "gaussian":
        return eta
    raise ValueError(f"the reference has no family {family!r}")


def fit(columns: Dict[str, np.ndarray], y: np.ndarray,
        smooths: Dict[str, Tuple[int, float]], linear: Sequence[str] = (),
        family: str = "binomial", w=None, tol: float = 1e-12,
        max_iter: int = 100) -> dict:
    """The penalized fit: ``smooths`` name -> (knots asked, scale),
    ``linear`` the unpenalized numeric terms.  Returns ``v`` (name ->
    the smooth's values at its knots), ``knots``, ``linear`` (name ->
    coefficient), ``intercept``, ``eta`` and the ``Smooth`` objects."""
    y = np.asarray(y, np.float64)
    w = np.ones_like(y) if w is None else np.asarray(w, np.float64)
    sm = {name: Smooth(columns[name], k, scale)
          for name, (k, scale) in smooths.items()}
    blocks = [s.B for s in sm.values()] + [
        np.asarray(columns[c], np.float64)[:, None] for c in linear]
    X = np.concatenate(blocks + [np.ones((len(y), 1))], axis=1)
    pen = np.zeros((X.shape[1], X.shape[1]))
    at = 0
    for s in sm.values():
        m = s.B.shape[1]
        pen[at:at + m, at:at + m] = s.lam * (s.Z.T @ s.S @ s.Z)
        at += m
    N = w.sum()
    beta = np.zeros(X.shape[1])
    if family == "binomial":
        ybar = np.sum(w * y) / N
        beta[-1] = np.log(ybar / (1 - ybar))
    for _ in range(max_iter):
        mu = _mu(family, X @ beta)
        W = w * (mu * (1 - mu) if family == "binomial" else 1.0)
        grad = X.T @ (w * (y - mu)) / N - pen @ beta
        step = np.linalg.solve(X.T @ (X * W[:, None]) / N + pen, grad)
        beta += step
        if np.max(np.abs(step)) < tol:
            break
    out = {"v": {}, "knots": {}, "linear": {}, "smooths": sm,
           "intercept": float(beta[-1]), "eta": X @ beta}
    at = 0
    for name, s in sm.items():
        m = s.B.shape[1]
        out["v"][name] = s.Z @ beta[at:at + m]
        out["knots"][name] = s.knots
        at += m
    for c in linear:
        out["linear"][c] = float(beta[at])
        at += 1
    return out


def optimality(columns: Dict[str, np.ndarray], y: np.ndarray,
               smooths: Dict[str, Tuple[int, float]],
               v: Dict[str, np.ndarray], intercept: float,
               linear: Dict[str, float] = None, family: str = "binomial",
               w=None) -> Dict[str, float]:
    """How far a fit given by its functions' values at the knots (``v``,
    on the reference's knots), ``intercept`` and ``linear`` coefficients is
    from the optimum, in float64: ``score``, the largest entry of the
    penalized score equations (the intercept's and the linear terms'
    X'(y - mu)/N, and for each smooth the part of B'(y - mu)/N - lambda S v
    off its centring direction), and ``centring``, the largest |c'v|."""
    y = np.asarray(y, np.float64)
    w = np.ones_like(y) if w is None else np.asarray(w, np.float64)
    linear = linear or {}
    sm = {name: Smooth(columns[name], k, scale)
          for name, (k, scale) in smooths.items()}
    eta = np.full(len(y), float(intercept))
    for name, s in sm.items():
        eta += cardinal(s.x, s.knots) @ v[name]
    for c, b in linear.items():
        eta += b * np.asarray(columns[c], np.float64)
    r = w * (y - _mu(family, eta)) / w.sum()
    score = [abs(r.sum())] + [abs(r @ np.asarray(columns[c], np.float64))
                              for c in linear]
    centring = []
    for name, s in sm.items():
        g = cardinal(s.x, s.knots).T @ r - s.lam * s.S @ v[name]
        score.append(np.max(np.abs(s.Z.T @ g)))
        centring.append(abs(s.c @ v[name]))
    return {"score": float(max(score)), "centring": float(max(centring))}
