"""GLM: generalized linear models with elastic-net regularization.

Reference: ``hex/glm/GLM.java:1573`` (GLMDriver; IRLSM:2143, L-BFGS:2757,
COD:2840), ``hex/glm/GLMTask.java`` (gradient/Hessian MRTasks),
``hex/gram/Gram.java:1017`` (distributed X'X accumulation, reduce = matrix
add, Cholesky on the driver), families/links in ``hex/glm/GLMModel.java:978``.

TPU-native redesign: IRLSM is one device program for the whole lambda path:
lambdas under ``lax.scan``, IRLS under ``lax.while_loop``, the small P x P
solve inside it (a linear solve for L2, coordinate descent on the Gram for
L1, the reference's IRLSM+COD strategy), one fetch at the end.  Its hot
loop, the Gram ``X^T diag(w) X``, runs on the MXU, and the shards' sums meet
in the ``psum`` that replaces GramTask's MRTask reduce.  The program has two
forms, and ``_dense_design_fits`` chooses from the frame and the device:

* ``_make_path_runner``, on the dense ``[rows, nfeatures]`` design
  (``DataInfo.make_matrix``), where that and one copy of it fit a quarter of
  the device: what every GLM ran before the code form, kept to the line.
* ``_make_blocked_path_runner``, on the design IN CODE FORM
  (``datainfo.CodedDesign``: numerics beside categorical codes), where the
  dense design does not fit: Gram, score and deviance are summed over row
  blocks, a block expanded to its one-hot columns where it is used, so a
  categorical of any cardinality costs 4 bytes a row.  Scoring walks the
  same blocks (``_make_score``).

Both are cached on what their programs close over, so a fit that repeats
an earlier fit's signature compiles nothing; ``cluster.init`` clears them
with the mesh.

``reference_glm.py`` is the same mathematics in plain ``jax.numpy`` on the
dense expansion.  L-BFGS, ordinal, multinomial (block-wise per-class Newton
steps on softmax probabilities, the COD-multinomial analog, GLM.java:1643)
and the ``non_negative`` host loop take the dense design only; they refuse a
frame whose expansion would not fit the device.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..frame.frame import Frame
from ..runtime import dkv
from ..runtime import observability as obs
from ..runtime.cluster import ROW_AXIS, cluster
from ..runtime.job import Job
from .base import Model, ModelBuilder, Parameters
from .datainfo import (CodedDesign, DataInfo, block_rows, coded_matvec,
                       coded_rmatvec, device_memory_bytes, expand_coded,
                       map_row_blocks, over_row_shards, sum_over_row_shards,
                       sum_row_blocks)
from ..metrics.core import make_metrics
from . import glm_gram


# ------------------------------------------------------------------- families
class _Family:
    """A family and its link.  Two families of one class and parameters are
    equal and hash alike: the compiled programs that close over one
    (``_make_path_runner`` and its kin) are cached on it, so every fit of a
    family reaches the same program."""
    name = "gaussian"

    def _key(self):
        return type(self), tuple(sorted(vars(self).items()))

    def __eq__(self, other):
        return isinstance(other, _Family) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def linkinv(self, eta):
        return eta

    def variance(self, mu):
        return jnp.ones_like(mu)

    def dlinkinv(self, eta, mu):
        """d mu / d eta."""
        return jnp.ones_like(eta)

    def deviance(self, y, mu, w):
        return jnp.sum(w * (y - mu) ** 2)

    def init_eta(self, y, w):
        mean = jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12)
        return jnp.full_like(y, mean)


class _Gaussian(_Family):
    pass


class _Binomial(_Family):
    name = "binomial"

    def linkinv(self, eta):
        return jax.nn.sigmoid(eta)

    def variance(self, mu):
        return mu * (1 - mu)

    def dlinkinv(self, eta, mu):
        return mu * (1 - mu)

    def deviance(self, y, mu, w):
        mu = jnp.clip(mu, 1e-15, 1 - 1e-15)
        return -2 * jnp.sum(w * (y * jnp.log(mu) + (1 - y) * jnp.log1p(-mu)))

    def init_eta(self, y, w):
        p = jnp.clip(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12),
                     1e-6, 1 - 1e-6)
        return jnp.full_like(y, jnp.log(p / (1 - p)))


class _Quasibinomial(_Binomial):
    name = "quasibinomial"


class _Poisson(_Family):
    name = "poisson"

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return mu

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-15)
        t = jnp.where(y > 0, y * jnp.log(y / mu), 0.0)
        return 2 * jnp.sum(w * (t - (y - mu)))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


class _Gamma(_Family):
    name = "gamma"

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return mu * mu

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-15)
        ys = jnp.maximum(y, 1e-15)
        return 2 * jnp.sum(w * (-jnp.log(ys / mu) + (ys - mu) / mu))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


class _Tweedie(_Family):
    name = "tweedie"

    def __init__(self, p: float):
        self.p = float(p)

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return jnp.power(jnp.maximum(mu, 1e-15), self.p)

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        p = self.p
        mu = jnp.maximum(mu, 1e-15)
        if p == 1.0:
            return _Poisson().deviance(y, mu, w)
        if p == 2.0:
            return _Gamma().deviance(y, mu, w)
        ys = jnp.maximum(y, 0.0)
        a = jnp.where(ys > 0,
                      jnp.power(jnp.maximum(ys, 1e-15), 2 - p) / ((1 - p) * (2 - p)),
                      0.0)
        b = ys * jnp.power(mu, 1 - p) / (1 - p)
        c = jnp.power(mu, 2 - p) / (2 - p)
        return 2 * jnp.sum(w * (a - b + c))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


class _NegativeBinomial(_Family):
    name = "negativebinomial"

    def __init__(self, theta: float):
        self.theta = float(theta)          # inverse dispersion

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return mu + self.theta * mu * mu

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-15)
        th = self.theta
        ys = jnp.maximum(y, 0.0)
        t1 = jnp.where(ys > 0, ys * jnp.log(ys / mu), 0.0)
        t2 = (ys + 1.0 / th) * jnp.log((1 + th * mu) / (1 + th * ys))
        return 2 * jnp.sum(w * (t1 + t2))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


def _make_family(name: str, params) -> _Family:
    if name == "tweedie":
        return _Tweedie(params.tweedie_variance_power)
    if name == "negativebinomial":
        return _NegativeBinomial(params.theta)
    return {"gaussian": _Gaussian, "binomial": _Binomial,
            "quasibinomial": _Quasibinomial, "poisson": _Poisson,
            "gamma": _Gamma}[name]()


# ------------------------------------------------------------------- kernels
def _ledger(name, jitted, orig=None):
    """Register a compiled GLM seam with the compile ledger (runtime/xprof)."""
    from ..runtime import xprof
    return xprof.register_program(name, jitted, orig=orig)


def _gram_kernel_impl(X, w):
    """Weighted Gram X'WX — the GramTask analog (gram/Gram.java:1017)."""
    Xw = X * w[:, None]
    return Xw.T @ X


_gram_kernel = _ledger("glm_gram", jax.jit(_gram_kernel_impl),
                       orig=_gram_kernel_impl)


# compiled GLM programs kept, each with its executables: a grid over a
# family's parameters, or RuleFit's fits of other rules, would otherwise
# hold every one of them for the life of the process
_PROGRAMS = 32


@functools.lru_cache(maxsize=_PROGRAMS)
def _make_irls_step(family: _Family):
    def step(X, y, w, beta, offset):
        eta = X @ beta + offset
        mu = family.linkinv(eta)
        g = jnp.maximum(family.dlinkinv(eta, mu), 1e-10)
        var = jnp.maximum(family.variance(mu), 1e-10)
        z = (eta - offset) + (y - mu) / g
        wi = w * g * g / var
        Xw = X * wi[:, None]
        gram = Xw.T @ X
        xtwz = Xw.T @ z
        dev = family.deviance(y, mu, w)
        return gram, xtwz, dev
    return _ledger("glm_irls", jax.jit(step), orig=step)


def _coordinate_descent(G, c, l1, l2, penalize, warm, max_inner: int):
    """Cyclic coordinate descent on the Gram (the reference's COD,
    GLM.java:2840) for ``0.5 b'Gb - c'b + l1'|b| + 0.5 l2'b^2`` from ``warm``,
    under a ``while_loop`` until no coefficient of a sweep moves by 1e-8:
    the penalized solve of both forms of the path program, traced where
    they call it."""
    d = jnp.diag(G)

    def sweep(state):
        beta, _, it = state

        def upd(j, bd):
            b, delta = bd
            r = c[j] - (G[j] @ b - d[j] * b[j])
            bj = jnp.where(
                penalize[j] > 0,
                jnp.sign(r) * jnp.maximum(jnp.abs(r) - l1[j], 0.0)
                / (d[j] + l2[j] + 1e-12),
                r / (d[j] + 1e-12))
            delta = jnp.maximum(delta, jnp.abs(bj - b[j]))
            return b.at[j].set(bj), delta

        beta2, delta = jax.lax.fori_loop(
            0, warm.shape[0], upd, (beta, jnp.float32(0.0)))
        return beta2, delta, it + 1

    def cond(state):
        _, delta, it = state
        return (it < max_inner) & (delta > 1e-8)

    beta, _, _ = jax.lax.while_loop(
        cond, sweep, (warm, jnp.float32(jnp.inf), 0))
    return beta


# a sweep of ``_group_coordinate_descent`` ends it when no move of the sweep
# changed a gradient entry by more: the float32 noise of a row's product
# with coefficients of order 1 (PERF.md, section 6)
CD_TOLERANCE = 1e-6


def _group_coordinate_descent(G, c, l1, l2, penalize, warm, max_inner: int,
                              runs: tuple, partition):
    """``_coordinate_descent``'s problem on a design of one-hot runs, a run
    at a time: RuleFit's lasso.  Returns the coefficients and the sweeps
    it ran.

    ``runs``, (first, width) of runs of one-hot columns: a row lights at
    most one column of a run, so the Gram is 0 between two columns of one
    run, and updating a run's coordinates together is updating them one
    after the other.  A sweep takes each run in one step (a [width, P]
    slice of the Gram), then every other coordinate alone: RuleFit's 400
    rule columns in 50 steps where one at a time took 400, each step
    costing about the same on a TPU.  A row's product with ``b`` is a
    float32 multiply and sum, which a TPU does not round to bfloat16 as it
    does a float32 ``dot`` at the default precision.

    A sweep ends the descent when no move of it is worth more than
    ``CD_TOLERANCE`` of the gradient (``G_jj`` times the move), at most
    ``max_inner`` sweeps.  The gradient and not the coefficient measures a
    move, because a float32 sweep over coefficients of order 1 moves some
    by an ulp each time (1e-7, where a limit of 1e-8 on the coefficient had
    every solve run its 100 sweeps), and a column that few rows light (a
    rule's raw 0/1 column) moves far for a small gradient.

    ``partition`` [runs] bool, on the device: the runs whose rows partition
    the frame's.  Such a run's columns sum to the intercept's (the last),
    so adding t to the run and taking t from the intercept leaves the
    design's products, and ``0.5 b'Gb - c'b``, as they are.  One
    coordinate or one run at a time moves along no such line, and on a
    design of many the sweeps stall short of the optimum.  So after every
    sweep each such run moves along its line to the least L1 penalty
    there: t at a weighted median of the run's -b_j, weights l1_j, the best
    of the run's own candidates (``l2`` is 0 on such runs: a lasso's)."""
    P = warm.shape[0]
    width = max(w for _, w in runs)
    slot = np.full((len(runs), width), P)       # P: a coordinate of zeros
    for g, (first, w) in enumerate(runs):
        slot[g, :w] = np.arange(first, first + w)
    lit = slot < P
    single = np.setdiff1d(np.arange(P), slot[lit])
    order = np.concatenate([slot.ravel(), single])
    back = np.empty(P, np.int64)
    back[order[order < P]] = np.flatnonzero(order < P)
    hot = slot.size

    def arranged(v):
        return jnp.concatenate([v, jnp.zeros((1,), v.dtype)])[order]

    Gp = jnp.pad(G, ((0, 1), (0, 1)))[order][:, order]
    cp, l1p, l2p, pen = (arranged(v) for v in (c, l1, l2, penalize))
    d = jnp.diag(Gp)

    def step(lo, size, bd):
        b, delta = bd

        def at(v):
            return jax.lax.dynamic_slice(v, (lo,), (size,))

        rows = jax.lax.dynamic_slice(Gp, (lo, 0), (size, Gp.shape[1]))
        bb, dd = at(b), at(d)
        r = at(cp) - (jnp.sum(rows * b[None, :], axis=1) - dd * bb)
        new = jnp.where(
            at(pen) > 0,
            jnp.sign(r) * jnp.maximum(jnp.abs(r) - at(l1p), 0.0)
            / (dd + at(l2p) + 1e-12),
            r / (dd + 1e-12))
        delta = jnp.maximum(delta, jnp.max(dd * jnp.abs(new - bb)))
        return jax.lax.dynamic_update_slice(b, new, (lo,)), delta

    def along_null_lines(b):
        B = b[:hot].reshape(slot.shape)
        w1 = l1p[:hot].reshape(slot.shape)
        cost = jnp.where(lit, jnp.sum(
            w1[:, None, :] * jnp.abs(B[:, None, :] - B[:, :, None]), axis=2),
            jnp.inf)
        best = jnp.argmin(cost, axis=1)
        shift = jnp.where(partition, jnp.take_along_axis(
            B, best[:, None], axis=1)[:, 0], 0.0)
        lower = jnp.max(jnp.where(
            partition, jnp.sum(w1 * jnp.abs(B), axis=1) - jnp.min(cost, axis=1),
            0.0))
        B = B - jnp.where(lit, shift[:, None], 0.0)
        b = b.at[:hot].set(B.reshape(-1)).at[back[P - 1]].add(jnp.sum(shift))
        return b, lower

    def sweep(state):
        b, _, it = state
        bd = jax.lax.fori_loop(0, len(runs), lambda g, bd: step(
            g * width, width, bd), (b, jnp.float32(0.0)))
        b, delta = jax.lax.fori_loop(hot, hot + single.size, lambda j, bd: step(
            j, 1, bd), bd)
        b, lower = along_null_lines(b)
        return b, jnp.maximum(delta, lower), it + 1

    def cond(state):
        _, delta, it = state
        return (it < max_inner) & (delta > CD_TOLERANCE)

    b, _, sweeps = jax.lax.while_loop(
        cond, sweep, (arranged(warm), jnp.float32(jnp.inf), jnp.int32(0)))
    return b[back], sweeps


def _l1_change(nb, beta, penalize, sweeps):
    """How far a pass of ``_group_coordinate_descent`` moved the
    coefficients, for ``beta_epsilon``.

    A coefficient's change is measured on the scale its penalty factor
    gives its column (1 for a standardised column, a raw 0/1 rule
    column's deviation; 1 where it is not penalized), so ``beta_epsilon``
    means what it means on a standardised design.  And the change is 0
    where the pass's coordinate descent ended after ONE sweep: no
    coordinate of the pass's starting beta was off its optimum by
    ``CD_TOLERANCE`` of the gradient, which the pass's Gram and score give
    exactly at that beta, so the lasso's optimality conditions hold there
    and IRLS stops.  Along a flat direction of the design (rules that
    light nearly the same rows) the coefficients move a little at every
    pass long after the gradient has settled, and ``beta_epsilon`` alone
    ran the passes to ``max_iterations``."""
    scale = jnp.where(penalize > 0, penalize, 1.0)
    return jnp.where(sweeps <= 1, 0.0, jnp.max(jnp.abs(nb - beta) * scale))


@functools.lru_cache(maxsize=_PROGRAMS)
def _make_path_runner(family: _Family, l1_mode: bool, max_iter: int,
                      max_inner: int = 100):
    """The WHOLE regularization path as one device program.

    The host loop pays a device->host round trip per IRLS iteration,
    which makes a long lambda path fetch-bound.  Here lambdas run under
    ``lax.scan`` with warm-started betas, IRLS under ``lax.while_loop``
    (beta_epsilon early exit), and the penalized solve on device: one
    linear solve for pure L2, cyclic coordinate descent (the reference's
    COD, GLM.java:2840) under a while_loop for any L1.  One fetch at the
    end returns per-lambda betas/deviances/iteration counts + the final
    Gram (p-values).
    """

    def irls_gram(X, y, w, beta, offset):
        eta = X @ beta + offset
        mu = family.linkinv(eta)
        g = jnp.maximum(family.dlinkinv(eta, mu), 1e-10)
        var = jnp.maximum(family.variance(mu), 1e-10)
        z = (eta - offset) + (y - mu) / g
        wi = w * g * g / var
        Xw = X * wi[:, None]
        return Xw.T @ X, Xw.T @ z, family.deviance(y, mu, w)

    def run(X, y, w, offset, lambdas, alpha, penalize, beta0, n,
            beta_eps):
        def solve(G, c, lam, warm):
            l2 = lam * (1 - alpha) * penalize
            if not l1_mode:
                A = G + jnp.diag(l2 + 1e-10)
                return jnp.linalg.solve(A, c)
            return _coordinate_descent(G, c, lam * alpha * penalize, l2,
                                       penalize, warm, max_inner)

        def per_lambda(beta, lam):
            def body(state):
                beta, _, it, _ = state
                gram, xtwz, dev = irls_gram(X, y, w, beta, offset)
                nb = solve(gram / n, xtwz / n, lam, beta)
                delta = jnp.max(jnp.abs(nb - beta))
                return nb, delta, it + 1, dev

            def cond(state):
                _, delta, it, _ = state
                return (it < max_iter) & (delta >= beta_eps)

            beta, _, iters, dev = jax.lax.while_loop(
                cond, body, (beta, jnp.float32(jnp.inf), 0,
                             jnp.float32(0.0)))
            return beta, (beta, dev, iters)

        beta_fin, (betas, devs, iters) = jax.lax.scan(
            per_lambda, beta0, lambdas)
        gram_fin, _, dev_fin = irls_gram(X, y, w, beta_fin, offset)
        return betas, devs, iters, gram_fin, dev_fin

    return _ledger("glm_path", jax.jit(run), orig=run)


# ------------------------------------------------- the code-form design
#
# Where the dense design does not fit the device, IRLSM never holds ``[rows,
# nfeatures]``: it reads ``CodedDesign`` (the numerics beside the
# categorical codes) a block of rows at a time.  A
# block's products with a coefficient or a row vector need no expansion
# (``coded_matvec`` / ``coded_rmatvec``: a one-hot column selects, every
# float32 product exact).  The Gram is the one product that needs the MXU:
# on a TPU the kernel of ``glm_gram`` forms it from the codes, elsewhere
# XLA's product of the block's one-hot columns, written out.

_HIGHEST = jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=None)
def _column_split(layout):
    """(columns of the one-hot blocks, the other columns) of the expanded
    layout, each in the layout's order, and the two layouts that expand
    them (``expand_coded`` takes a layout of some of the runs)."""
    cat, rest, at = [], [], 0
    for kind, width in layout:
        (cat if kind == "cat" else rest).extend(range(at, at + width))
        at += width
    return (np.asarray(cat, np.int32), np.asarray(rest, np.int32),
            tuple(r for r in layout if r[0] == "cat"),
            tuple(r for r in layout if r[0] != "cat"))


def _bf16_pieces(a):
    """``a`` (float32) as a sum of three bfloat16 arrays, which hold all 24
    bits of a float32 mantissa.  ``reduce_precision`` and not a cast there
    and back, which the compiler may take for the identity."""
    out = []
    for _ in range(3):
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        out.append(hi.astype(jnp.bfloat16))
        a = a - hi
    return out


def _gram_parts(layout, xr, nb, cb, wi):
    """X' diag(wi) X of one block, as the parts the MXU forms: ``top``
    [one-hot columns, one-hot columns then the others] and ``rest`` [the
    others, the others]; ``_gram_of_parts`` puts them in the layout's
    order.  ``xr`` are the block's other columns, expanded.

    A one-hot column is exact in bfloat16, so ``top`` is bfloat16 products
    of the one-hot block with ``wi o X`` in three bfloat16 pieces, summed in
    float32: each float32 product exact, three passes of the MXU where the
    float32 product at ``highest`` takes six.  Of ``wi o X`` the one-hot
    part is the pieces of ``wi`` itself where the column is lit.  Where
    ``glm_gram.engages`` (a TPU), its kernel forms the same products from
    the codes, and ``top`` is the tuple of its sums.  Beside
    one-hot blocks the few other columns (numerics, intercept) multiply at
    ``highest``, a few percent of the block's work; a frame with no
    categorical (``top`` is None) keeps the product it always had, the
    compiler's default for float32 (six passes there would be the whole of
    its Gram)."""
    cat_layout = _column_split(layout)[2]
    yr = xr * wi[:, None]
    if not cat_layout:
        return None, jnp.dot(xr.T, yr)
    rest = jnp.dot(xr.T, yr, precision=_HIGHEST)
    if glm_gram.engages(layout):
        return glm_gram.gram_parts(
            layout, cb, _bf16_pieces(jnp.concatenate([wi[:, None], yr],
                                                     axis=1))), rest
    hot = expand_coded(cat_layout, nb, cb).astype(jnp.bfloat16)
    top = sum(
        jnp.dot(hot.T, jnp.concatenate([hot * wk[:, None], yk], axis=1),
                preferred_element_type=jnp.float32)
        for wk, yk in zip(_bf16_pieces(wi), _bf16_pieces(yr)))
    return top, rest


def _gram_of_parts(layout, top, rest):
    """The Gram in the layout's column order from ``_gram_parts``' two
    (summed over blocks and shards first: this runs once a pass)."""
    if top is None:
        return rest
    if isinstance(top, tuple):                  # the kernel's sums
        top = glm_gram.top_of_parts(layout, top)
    cat, other, _, _ = _column_split(layout)
    nc = len(cat)
    both = jnp.concatenate([
        top, jnp.concatenate([top[:, nc:].T, rest], axis=1)], axis=0)
    back = np.argsort(np.concatenate([cat, other]))
    return both[back][:, back]


def _make_irls_gram(family: _Family, layout: tuple, block: int):
    """``irls_gram(num, codes, y, w, offset, beta) -> (X'WX, X's, deviance)``
    at ``beta``, over the row blocks of the code-form design: every
    row-shard walks its rows in blocks of ``block`` (``sum_row_blocks``),
    expands one, and the shards' sums meet in a ``psum``
    (``sum_over_row_shards``).  ``W`` are the
    IRLS weights and ``s = w g (y - mu) / var`` the score's, so that
    ``X'Wz = X'WX beta + X's`` for the working response ``z``."""
    rest_layout = _column_split(layout)[3]

    def sums_of(beta, wb, nb, cb, yb, ob):
        eta = coded_matvec(layout, nb, cb, beta) + ob
        mu = family.linkinv(eta)
        g = jnp.maximum(family.dlinkinv(eta, mu), 1e-10)
        var = jnp.maximum(family.variance(mu), 1e-10)
        score = coded_rmatvec(layout, nb, cb, wb * g * (yb - mu) / var)
        xr = expand_coded(rest_layout, nb, cb)
        return (_gram_parts(layout, xr, nb, cb, wb * g * g / var), score,
                family.deviance(yb, mu, wb))

    def shard(num, codes, y, w, offset, beta):
        return sum_over_row_shards(sum_row_blocks(
            functools.partial(sums_of, beta), block, w, num, codes, y, offset))

    def irls_gram(num, codes, y, w, offset, beta):
        rows, mat = P(ROW_AXIS), P(ROW_AXIS, None)
        (top, rest), score, dev = over_row_shards(
            shard, in_specs=(mat, mat, rows, rows, rows, P()),
            out_specs=P())(num, codes, y, w, offset, beta)
        return _gram_of_parts(layout, top, rest), score, dev

    return irls_gram


def _fit_block_rows(layout: tuple, padded_rows: int) -> int:
    """Rows of one block of the IRLSM walk over a frame of ``padded_rows``
    (a shard walks its share of them): the expanded row and its weighted
    copy in float32, and where the layout has one-hot blocks their bfloat16
    operands (three pieces and the block itself), inside a quarter of the
    device: a frame whose expansion fits there is one block."""
    width = sum(w for _, w in layout)
    hot = sum(w for kind, w in layout if kind == "cat")
    return block_rows(4 * 2 * width + 2 * (3 * width + hot if hot else 0),
                      padded_rows // cluster().n_row_shards, share=4)


@functools.lru_cache(maxsize=_PROGRAMS)
def _make_blocked_path_runner(family: _Family, l1_mode: bool, max_iter: int,
                              layout: tuple, block: int,
                              max_inner: int = 100, runs: tuple = ()):
    """``_make_path_runner``'s program on the design in code form, cached
    as it is (the Gram's kernel, ``glm_gram.engages``, follows from the
    layout and the mesh, whose rebuild clears the cache): the same scan
    over lambdas, ``while_loop`` of IRLS passes, solve on the device and
    one fetch of the same five results.  It departs in two things.

    Every IRLS pass reads the design in row blocks of ``block``
    (``_make_irls_gram``), and the pass of the final Gram is a closing step
    of the scan, which updates nothing: the program holds, and a fit
    traces, one IRLS pass and one solve.

    The L2 solve is for the Newton STEP, ``(G + D) delta = X's/n - D beta``:
    algebraically the IRLS update ``(G + D) beta' = X'Wz/n``, but a float32
    solve's error then scales with the step and vanishes at convergence,
    where solved for ``beta'`` it scales with cond(G) |beta'| and stays (a
    wide one-hot design beside an intercept is not well conditioned).

    With ``runs`` (RuleFit's rule groups: ``_group_coordinate_descent``) an
    L1 program takes one more argument, the runs' ``partition`` flags,
    solves by ``_group_coordinate_descent``, ends a lambda's passes by
    ``_l1_change`` and returns a sixth result, the sweeps of all its
    solves.
    """
    irls_gram = _make_irls_gram(family, layout, block)
    grouped = l1_mode and bool(runs)

    def run(num, codes, y, w, offset, lambdas, alpha, penalize, beta0, n,
            beta_eps, *partition):
        n_coef = beta0.shape[0]

        def solve(G, score, lam, beta):
            l2 = lam * (1 - alpha) * penalize
            if not l1_mode:
                ridge = l2 + 1e-10
                return beta + jnp.linalg.solve(G + jnp.diag(ridge),
                                               score - ridge * beta)
            c = jnp.dot(G, beta, precision=_HIGHEST) + score    # X'Wz / n
            if grouped:
                return _group_coordinate_descent(
                    G, c, lam * alpha * penalize, l2, penalize, beta,
                    max_inner, runs, partition[0])
            return _coordinate_descent(G, c, lam * alpha * penalize, l2,
                                       penalize, beta, max_inner)

        def per_lambda(carry, step):
            beta, _ = carry
            lam, closing = step

            def body(state):
                beta, _, it, _, _ = state[:5]
                gram, score, dev = irls_gram(num, codes, y, w, offset, beta)
                if not grouped:
                    nb = jnp.where(closing, beta,
                                   solve(gram / n, score / n, lam, beta))
                    delta = jnp.max(jnp.abs(nb - beta))
                    return nb, delta, it + 1, dev, gram
                # the closing pass solves nothing: no sweeps are run
                nb, sweeps = jax.lax.cond(
                    closing, lambda: (beta, jnp.int32(0)),
                    lambda: solve(gram / n, score / n, lam, beta))
                return (nb, _l1_change(nb, beta, penalize, sweeps), it + 1,
                        dev, gram, state[5] + sweeps)

            def cond(state):
                _, delta, it, _, _ = state[:5]
                return (it < jnp.where(closing, 1, max_iter)) \
                    & (delta >= beta_eps)

            beta, _, iters, dev, gram, *sweeps = jax.lax.while_loop(
                cond, body, (beta, jnp.float32(jnp.inf), 0, jnp.float32(0.0),
                             jnp.zeros((n_coef, n_coef), jnp.float32)) + (
                                 (jnp.int32(0),) if grouped else ()))
            return (beta, gram), (beta, dev, iters, *sweeps)

        # the path's lambdas, then the closing step: ONE pass at the final
        # beta, whose Gram (p-values) and deviance are the program's last two
        steps = (jnp.concatenate([lambdas, lambdas[-1:]]),
                 jnp.arange(lambdas.shape[0] + 1) == lambdas.shape[0])
        (_, gram_fin), (betas, devs, iters, *sweeps) = jax.lax.scan(
            per_lambda, (beta0, jnp.zeros((n_coef, n_coef), jnp.float32)),
            steps)
        return (betas[:-1], devs[:-1], iters[:-1], gram_fin, devs[-1]) \
            + tuple(jnp.sum(s) for s in sweeps)

    return _ledger("glm_path", jax.jit(run), orig=run)


@functools.lru_cache(maxsize=None)
def _make_xtv(layout: tuple, block: int):
    """Compiled ``X'v`` for a row vector ``v`` (zero on padded rows) over
    the row blocks of the code-form design."""
    def sums_of(vb, nb, cb):
        return coded_rmatvec(layout, nb, cb, vb)

    def shard(v, num, codes):
        return sum_over_row_shards(
            sum_row_blocks(sums_of, block, v, num, codes))

    def glm_xtv(v, num, codes):
        return over_row_shards(
            shard, in_specs=(P(ROW_AXIS), P(ROW_AXIS, None),
                             P(ROW_AXIS, None)),
            out_specs=P())(v, num, codes)

    return jax.jit(glm_xtv)


@functools.lru_cache(maxsize=None)
def _make_score(layout: tuple, family: str, classifier: bool, block: int):
    """Compiled scoring program, cached on what it closes over: every
    row-shard walks its own rows in blocks of ``block``
    (``map_row_blocks``), expands one and keeps what ``_predict_raw``
    returns for it.  ``beta`` is [P] or, multinomial, [P, K]; ``thetas``
    the ordinal thresholds (empty for every other family)."""
    def rows_of(beta, thetas, nb, cb):
        if family == "multinomial":
            return jax.nn.softmax(jnp.dot(expand_coded(layout, nb, cb), beta,
                                          precision=_HIGHEST), axis=1)
        eta = coded_matvec(layout, nb, cb, beta)
        if family == "ordinal":              # the intercept's beta is 0
            cdf = jax.nn.sigmoid(thetas[None, :] - eta[:, None])
            cdf = jnp.concatenate(
                [jnp.zeros((cdf.shape[0], 1)), cdf,
                 jnp.ones((cdf.shape[0], 1))], axis=1)
            return jnp.clip(jnp.diff(cdf, axis=1), 0.0, 1.0)
        # no link reads the family's own parameters
        mu = _make_family(family, GLMParameters()).linkinv(eta)
        return jnp.stack([1 - mu, mu], axis=1) if classifier else mu

    def shard(beta, thetas, num, codes):
        return map_row_blocks(functools.partial(rows_of, beta, thetas),
                              block, num, codes)

    # the name the device trace knows the program by: jit_glm_score
    def glm_score(beta, thetas, num, codes):
        regression = family not in ("multinomial", "ordinal") and not classifier
        return over_row_shards(
            shard, in_specs=(P(), P(), P(ROW_AXIS, None), P(ROW_AXIS, None)),
            out_specs=P(ROW_AXIS) if regression else P(ROW_AXIS, None))(
                beta, thetas, num, codes)

    return jax.jit(glm_score)


def _make_softmax_stats(nclasses: int):
    def stats(X, y, w, beta, offset):
        """Per-class diagonal-block Newton quantities for multinomial."""
        eta = X @ beta + offset[:, None]
        probs = jax.nn.softmax(eta, axis=1)
        yi = jnp.clip(y.astype(jnp.int32), 0, nclasses - 1)
        Y = jax.nn.one_hot(yi, nclasses)
        p_true = jnp.clip(probs[jnp.arange(probs.shape[0]), yi], 1e-15, 1.0)
        ll = -jnp.sum(w * jnp.log(p_true))
        grams, xtwz = [], []
        for k in range(nclasses):
            mu = probs[:, k]
            wk = jnp.maximum(w * mu * (1 - mu), 1e-10 * w)
            zk = eta[:, k] - offset + (Y[:, k] - mu) / jnp.maximum(
                mu * (1 - mu), 1e-10)
            Xw = X * wk[:, None]
            grams.append(Xw.T @ X)
            xtwz.append(Xw.T @ zk)
        return jnp.stack(grams), jnp.stack(xtwz).T, ll, probs
    return _ledger("glm_softmax", jax.jit(stats), orig=stats)


# -------------------------------------------------------------------- solver
def _solve_penalized(gram: np.ndarray, xtwz: np.ndarray, n: float,
                     lam: float, alpha: float, beta0: np.ndarray,
                     penalize: np.ndarray, max_inner: int = 100,
                     tol: float = 1e-8,
                     nonneg: Optional[np.ndarray] = None) -> np.ndarray:
    """Solve 0.5 b'Gb - c'b + lam*(alpha*|b|_1 + (1-alpha)/2 |b|_2^2).

    G = gram/n, c = xtwz/n.  Pure L2 -> one Cholesky solve; any L1 or
    sign constraint -> cyclic coordinate descent on the Gram (the
    reference's COD, GLM.java:2840).  ``penalize`` masks out the
    intercept; ``nonneg`` marks coefficients clamped to >= 0 (the GLM
    ``non_negative`` option — per-coordinate projection, which for CD is
    the exact constrained minimizer).
    """
    G = gram / n
    c = xtwz / n
    # ``penalize`` is a per-coefficient penalty FACTOR (glmnet-style):
    # 0 = unpenalized (intercept, spline null space), 1 = standard, other
    # values scale both the L1 and L2 shares (GAM penalty eigenvalues)
    l2 = lam * (1 - alpha) * penalize
    l1 = lam * alpha * penalize
    constrained = nonneg is not None and bool(np.any(nonneg))
    if np.all(l1 == 0.0) and not constrained:
        A = G + np.diag(l2 + 1e-10)
        try:
            return np.linalg.solve(A, c)
        except np.linalg.LinAlgError:
            return np.linalg.lstsq(A, c, rcond=None)[0]
    beta = beta0.copy()
    if constrained:
        beta[nonneg] = np.maximum(beta[nonneg], 0.0)
    d = np.diag(G).copy()
    Gb = G @ beta
    for _ in range(max_inner):
        delta = 0.0
        for j in range(len(beta)):
            r = c[j] - (Gb[j] - d[j] * beta[j])
            if penalize[j] > 0:
                bj = np.sign(r) * max(abs(r) - l1[j], 0.0) \
                    / (d[j] + l2[j] + 1e-12)
            else:
                bj = r / (d[j] + 1e-12)
            if constrained and nonneg[j]:
                bj = max(bj, 0.0)
            diff = bj - beta[j]
            if diff != 0.0:
                Gb += G[:, j] * diff
                delta = max(delta, abs(diff))
                beta[j] = bj
        if delta < tol:
            break
    return beta


def _dense_design_fits(frame: Frame, di: DataInfo) -> bool:
    """Whether a device holds its rows of the dense ``[rows, nfeatures]``
    design and one copy for a solver's products inside a quarter of its
    memory.  Where it does, IRLSM and scoring run on the dense design as they
    always have (the narrow GLM fit is the same program, to the line); where
    it does not, they read the design in code form, block by block."""
    rows = frame.padded_rows // cluster().n_row_shards
    return 2 * rows * di.nfeatures * 4 <= device_memory_bytes() // 4


def _refuse_dense_design(what: str, frame: Frame, di: DataInfo) -> None:
    """Raise, before anything is allocated, where a solver that takes the
    dense ``[rows, nfeatures]`` design meets a frame whose expansion (with
    one copy for the solver's products) passes the device's memory."""
    rows = frame.padded_rows // cluster().n_row_shards
    nbytes, have = rows * di.nfeatures * 4, device_memory_bytes()
    if 2 * nbytes > have:
        raise ValueError(
            f"GLM with {what} takes the dense one-hot design: {rows:,} rows "
            f"x {di.nfeatures:,} columns x 4 B = {nbytes:,} bytes a device, "
            f"of {have:,} it has.  solver='irlsm' (binomial, gaussian, "
            f"poisson, gamma, tweedie, negativebinomial; lambda path and "
            f"elastic net included, without non_negative) reads the design "
            f"in code form and never expands the frame.")


# ---------------------------------------------------------------- parameters
@dataclasses.dataclass
class GLMParameters(Parameters):
    family: str = "auto"                  # auto|gaussian|binomial|quasibinomial|
    # poisson|gamma|tweedie|negativebinomial|multinomial
    alpha: float = 0.5
    lambda_: Union[float, Sequence[float], None] = None   # None -> 0 / search
    lambda_search: bool = False
    nlambdas: int = 30
    lambda_min_ratio: float = 1e-4
    solver: str = "irlsm"
    # sign constraint (GLMParameters._non_negative): True = every
    # non-intercept coefficient >= 0; a list of column names constrains
    # only those columns (monotone GAM splines ride this)
    non_negative: Union[bool, Sequence[str]] = False
    # per-column penalty factors {column: factor}; cat columns apply the
    # factor to every one-hot slot (glmnet penalty.factor / GAM penalties)
    penalty_factors: Optional[dict] = None
    tweedie_variance_power: float = 1.5
    theta: float = 1.0                    # negative binomial
    beta_epsilon: float = 1e-5
    compute_p_values: bool = False
    intercept: bool = True
    max_iterations: int = 50


class GLMModel(Model):
    algo = "glm"

    def _score_matrix(self, frame: Frame):
        """The design ``_predict_raw`` expects: the dense one where it fits
        the device beside its products, else in code form."""
        if _dense_design_fits(frame, self.datainfo):
            return self.datainfo.make_matrix(frame)
        return self.datainfo.make_coded(frame)

    def _predict_raw(self, X) -> jax.Array:
        """Scores of every row of ``X``: of a dense design in eager
        programs, of a code-form design in row blocks sized from the
        expanded width and the device's memory, so that the dense rows
        exist for the block in hand only."""
        if not isinstance(X, CodedDesign):
            return self._predict_dense(X)
        di, family = self.datainfo, self.output["family"]
        beta = jnp.asarray(self.output["beta_std"], jnp.float32)
        thetas = jnp.asarray(self.output.get("ordinal_thresholds", ()),
                             jnp.float32)
        rows = X.num.shape[0] // cluster().n_row_shards
        width = beta.shape[0] + (beta.shape[1] if beta.ndim == 2
                                 else thetas.shape[0] + 2)
        score = _make_score(di.coded_layout(), family, di.is_classifier,
                            block_rows(2 * 4 * width, rows))
        return score(beta, thetas, X.num, X.codes)

    def _predict_dense(self, X: jax.Array) -> jax.Array:
        """Scores of the dense design's rows, in eager programs."""
        beta = jnp.asarray(self.output["beta_std"])
        family = self.output["family"]
        if family == "multinomial":
            probs = jax.nn.softmax(X @ beta, axis=1)
            return probs
        if family == "ordinal":
            thetas = jnp.asarray(self.output["ordinal_thresholds"])
            eta = X @ beta                    # intercept col has beta 0
            cdf = jax.nn.sigmoid(thetas[None, :] - eta[:, None])
            cdf = jnp.concatenate(
                [jnp.zeros((cdf.shape[0], 1)), cdf,
                 jnp.ones((cdf.shape[0], 1))], axis=1)
            return jnp.clip(jnp.diff(cdf, axis=1), 0.0, 1.0)
        eta = X @ beta
        fam = _make_family(family, self.params)
        mu = fam.linkinv(eta)
        if self.datainfo.is_classifier:
            return jnp.stack([1 - mu, mu], axis=1)
        return mu

    @property
    def coef(self) -> dict:
        return dict(zip(self.output["coef_names"], self.output["beta"]))

    @property
    def coef_norm(self) -> dict:
        return dict(zip(self.output["coef_names"], self.output["beta_std_flat"]))


class GLM(ModelBuilder):
    """GLM builder — h2o.glm / H2OGeneralizedLinearEstimator analog."""

    algo = "glm"
    model_class = GLMModel

    def __init__(self, params: Optional[GLMParameters] = None, **kw):
        super().__init__(params or GLMParameters(**kw))

    def _resolve_family(self, di: DataInfo) -> str:
        fam = self.params.family
        if fam in ("auto", None):
            if di.is_classifier:
                fam = "binomial" if di.nclasses == 2 else "multinomial"
            else:
                fam = "gaussian"
        if fam in ("binomial", "quasibinomial") and not di.is_classifier:
            raise ValueError(f"family={fam} needs a categorical response")
        if fam == "multinomial" and di.nclasses < 3:
            fam = "binomial"
        if fam == "ordinal" and (not di.is_classifier or di.nclasses < 3):
            raise ValueError("family=ordinal needs a categorical response "
                             "with 3+ ordered levels")
        return fam

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> GLMModel:
        p: GLMParameters = self.params
        fam_name = self._resolve_family(di)
        P = di.nfeatures
        penalize = np.ones(P)
        if di.add_intercept:
            penalize[-1] = 0.0
        if p.penalty_factors:
            for spec in di.specs:
                f = p.penalty_factors.get(spec.name)
                if f is not None:
                    penalize[spec.offset: spec.offset + spec.width] = f
        nonneg = np.zeros(P, dtype=bool)
        if p.non_negative is True:
            nonneg[:] = True
            if di.add_intercept:
                nonneg[-1] = False
        elif p.non_negative:
            want = set(p.non_negative)
            matched = set()
            for spec in di.specs:
                if spec.name in want:
                    nonneg[spec.offset: spec.offset + spec.width] = True
                    matched.add(spec.name)
            if want - matched:
                raise ValueError(
                    f"non_negative names not in the design: "
                    f"{sorted(want - matched)}")
        lbfgs = p.solver.lower() in ("l_bfgs", "lbfgs")
        if nonneg.any() and (fam_name in ("multinomial", "ordinal") or lbfgs):
            raise ValueError("non_negative requires the IRLSM/COD solver "
                             "on a non-multinomial family")
        self._nonneg = nonneg if nonneg.any() else None

        # IRLSM's device program can read the design in code form, and does
        # where the dense expansion would not fit; the other solvers take
        # the dense one or refuse
        dense = ("family=" + fam_name if fam_name in ("multinomial", "ordinal")
                 else "solver=l_bfgs" if lbfgs
                 else "non_negative" if nonneg.any() else None)
        with obs.span("glm.matrix"):
            if dense is None and not _dense_design_fits(frame, di):
                X = di.make_coded(frame)
            else:
                if dense is not None:
                    _refuse_dense_design(dense, frame, di)
                X = di.make_matrix(frame)
            y = di.response(frame)
            w = di.weights(frame)
            y = jnp.nan_to_num(y)
            offset = di.offsets(frame)
            offset = offset if offset is not None else jnp.zeros_like(y)
            n = float(jnp.sum(w))

        if fam_name == "ordinal":
            lam0 = 0.0 if p.lambda_ is None else float(np.max(p.lambda_))
            return self._fit_ordinal(job, frame, di, X, y, w, offset, n,
                                     lam0, valid)
        lambdas = self._lambda_path(p, X, y, w, di, fam_name)
        if fam_name == "multinomial":
            model = self._fit_multinomial(job, frame, di, X, y, w, offset, n,
                                          penalize, lambdas, valid)
        else:
            model = self._fit_single(job, frame, di, X, y, w, offset, n,
                                     penalize, lambdas, fam_name, valid)
        return model

    # -------------------------------------------------------- lambda path
    def fit_coded(self, job: Job, frame: Frame, di: DataInfo,
                  X: CodedDesign, y, w, offset, penalize: np.ndarray,
                  runs: tuple, partition: np.ndarray) -> GLMModel:
        """IRLSM's path on a design its caller built in code form, under
        ``di`` (whose ``coded_layout`` it has), with the caller's response,
        weights, offset and per-coefficient penalty factors: the blocked
        runner whatever the design's size, and the same ``_fit_single``,
        spans and ``_finalize`` as ``train``.  ``runs``, (first, width) of
        the design's runs of one-hot columns, and ``partition``, which of
        them partition the frame's rows, go to a lasso's
        ``_group_coordinate_descent``; the lambda path is taken on the
        penalty factors (``_lambda_path``).  RuleFit fits its rules so."""
        p: GLMParameters = self.params
        fam_name = self._resolve_family(di)
        with obs.span("glm.matrix"):
            n = float(jnp.sum(w))
        lambdas = self._lambda_path(p, X, y, w, di, fam_name,
                                    factors=penalize)
        return self._fit_single(job, frame, di, X, y, w, offset, n, penalize,
                                lambdas, fam_name, None, runs=runs,
                                partition=partition)

    def _lambda_path(self, p: GLMParameters, X, y, w, di, fam_name,
                     factors: Optional[np.ndarray] = None) -> List[float]:
        if p.lambda_ is not None and not p.lambda_search:
            return list(np.atleast_1d(np.asarray(p.lambda_, dtype=np.float64)))
        if not p.lambda_search:
            return [0.0]
        # lambda_max: smallest lambda zeroing all coefs = max |X'(y-ybar)|/(n*alpha);
        # with penalty ``factors``, max_j |X_j'(y-ybar)| / (n * alpha * factor
        # j) over the penalized coefficients (glmnet's convention)
        fam = _make_family(fam_name, p)
        eta0 = fam.init_eta(y, w)
        mu0 = fam.linkinv(eta0)
        v = w * (y - mu0)
        if isinstance(X, CodedDesign):
            layout = di.coded_layout()
            xtv = _make_xtv(layout, _fit_block_rows(layout, v.shape[0]))
            grad = np.asarray(jnp.abs(xtv(v, *X)))
        else:
            grad = np.asarray(jnp.abs(X.T @ v))
        if factors is not None:
            factor = np.asarray(factors, np.float64)
            grad = grad[factor > 0] / factor[factor > 0]
        elif di.add_intercept:
            grad = grad[:-1]
        n = max(float(jnp.sum(w)), 1.0)
        lmax = float(grad.max(initial=0.0)) / max(p.alpha, 1e-3) / n
        lmin = lmax * p.lambda_min_ratio
        return list(np.geomspace(lmax, lmin, p.nlambdas))

    # ------------------------------------------------------------- l-bfgs
    def _fit_lbfgs(self, job, frame, di, X, y, w, offset, n, penalize,
                   lam, fam_name, valid) -> "GLMModel":
        """L-BFGS solver — GLM.java:2757's solver=L_BFGS analog.

        Minimizes deviance/(2n) + lam*(1-alpha)/2 |b|_2^2 with optax's
        L-BFGS inside one jit-compiled scan (the whole optimization is a
        single device program).  Like the reference without ADMM, L1 is
        not supported on this solver — use IRLSM/COD for alpha > 0.
        """
        import optax
        from ..runtime.observability import log
        p: GLMParameters = self.params
        if p.alpha > 0 and (np.asarray(lam) > 0).any():
            # reference behavior: L_BFGS defaults alpha to 0 (no L1 without
            # ADMM); drop the L1 component rather than failing
            log.warning("solver='lbfgs' ignores the L1 component "
                        "(alpha=%s); keeping the L2 share", p.alpha)
        fam = _make_family(fam_name, p)
        pen = jnp.asarray(penalize, jnp.float32)
        lamf = float(lam)

        def obj(beta):
            eta = X @ beta + offset
            mu = fam.linkinv(eta)
            dev = fam.deviance(y, mu, w)
            return dev / (2 * n) + 0.5 * lamf * jnp.sum(pen * beta ** 2)

        opt = optax.lbfgs()
        vg = optax.value_and_grad_from_state(obj)

        iters = int(min(p.max_iterations, 100))

        @jax.jit
        def run(beta0):
            state = opt.init(beta0)

            def step_fn(carry, _):
                params, st = carry
                value, grad = vg(params, state=st)
                updates, st = opt.update(grad, st, params, value=value,
                                         grad=grad, value_fn=obj)
                params = optax.apply_updates(params, updates)
                return (params, st), value
            (beta, _), values = jax.lax.scan(step_fn, (beta0, state),
                                             None, length=iters)
            return beta, values

        P = di.nfeatures
        beta0 = jnp.zeros(P, jnp.float32)
        if di.add_intercept:
            beta0 = beta0.at[-1].set(fam.init_eta(y, w)[0])
        beta_j, values = run(beta0)
        beta = np.asarray(beta_j, np.float64)
        hist = [{"lambda": lamf, "iteration": i,
                 "deviance": float(v) * 2 * n, "delta": float("nan")}
                for i, v in enumerate(np.asarray(values))]
        # gram at the solution (p-values / std errors in _finalize)
        step = _make_irls_step(fam)
        gram, _, dev = step(X, y, w, jnp.asarray(beta, jnp.float32), offset)
        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        self._finalize(model, di, beta, fam_name, X, y, w, n,
                       float(dev), hist, lamf, frame, valid,
                       gram_last=np.asarray(gram, np.float64))
        return model

    # ----------------------------------------------------------- ordinal
    def _fit_ordinal(self, job, frame, di, X, y, w, offset, n, lam,
                     valid) -> "GLMModel":
        """Proportional-odds (cumulative logit) — GLM.java family=ordinal.

        P(y <= j) = sigmoid(theta_j - X beta) with ordered thresholds,
        fit jointly by L-BFGS on the NLL inside one jit scan; thresholds
        are parameterized as theta_0 + cumulative softplus gaps so the
        ordering constraint holds by construction.
        """
        import optax
        p: GLMParameters = self.params
        K = di.nclasses
        P = di.nfeatures
        # drop the intercept column (absorbed into the thresholds)
        has_icpt = di.add_intercept
        Xf = X[:, :-1] if has_icpt else X
        Pf = Xf.shape[1]
        yi = jnp.clip(y.astype(jnp.int32), 0, K - 1)
        lamf = float(lam)

        def unpack(params):
            beta = params[:Pf]
            t0 = params[Pf]
            gaps = jax.nn.softplus(params[Pf + 1:])
            thetas = t0 + jnp.concatenate(
                [jnp.zeros(1), jnp.cumsum(gaps)])
            return beta, thetas

        def nll_fn(params):
            beta, thetas = unpack(params)
            eta = Xf @ beta + offset
            # cdf_j = P(y <= j), j = 0..K-2; boundaries 0 and 1 appended
            cdf = jax.nn.sigmoid(thetas[None, :] - eta[:, None])
            cdf = jnp.concatenate(
                [jnp.zeros((cdf.shape[0], 1)), cdf,
                 jnp.ones((cdf.shape[0], 1))], axis=1)
            probs = jnp.clip(jnp.diff(cdf, axis=1), 1e-12, 1.0)
            pick = jnp.take_along_axis(probs, yi[:, None], 1)[:, 0]
            return -jnp.sum(w * jnp.log(pick)) / n

        def obj(params):
            beta, _ = unpack(params)
            return nll_fn(params) + 0.5 * lamf * jnp.sum(beta ** 2)

        opt = optax.lbfgs()
        vg = optax.value_and_grad_from_state(obj)
        iters = int(min(p.max_iterations * 4, 200))

        @jax.jit
        def run(p0):
            state = opt.init(p0)

            def step(carry, _):
                prm, st = carry
                value, grad = vg(prm, state=st)
                upd, st = opt.update(grad, st, prm, value=value, grad=grad,
                                     value_fn=obj)
                return (optax.apply_updates(prm, upd), st), value
            (prm, _), values = jax.lax.scan(step, (p0, state), None,
                                            length=iters)
            return prm, values

        p0 = jnp.concatenate([jnp.zeros(Pf),
                              jnp.asarray([-1.0]),
                              jnp.full(K - 2, 0.5)]).astype(jnp.float32)
        prm, values = run(p0)
        beta, thetas = unpack(prm)
        final_nll = float(nll_fn(prm))     # penalty-free, at the FINAL point

        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        beta_full = np.zeros(P)
        beta_full[:Pf] = np.asarray(beta, np.float64)
        # destandardize for reporting (what _finalize does elsewhere)
        beta_orig = beta_full.copy()
        if di.standardize:
            ci = 0
            for spec in di.specs:
                if spec.type != "cat" and spec.width == 1 \
                        and ci < Pf and spec.sigma:
                    beta_orig[ci] = beta_full[ci] / spec.sigma
                ci += spec.width
        model.output.update({
            "family": "ordinal",
            "beta_std": beta_full,
            "ordinal_thresholds": np.asarray(thetas, np.float64),
            "coef_names": di.coef_names,
            "beta_std_flat": beta_full.tolist(),
            "beta": beta_orig.tolist(),
            "iterations": iters,
            "residual_deviance": final_nll * 2 * n,
        })
        model.scoring_history = [
            {"iteration": i, "deviance": float(v) * 2 * n}
            for i, v in enumerate(np.asarray(values[-5:]))]
        raw = model._predict_raw(X)
        model.training_metrics = make_metrics(di, raw, y, w)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model

    # ------------------------------------------------------- single-class
    def _fit_single(self, job, frame, di, X, y, w, offset, n, penalize,
                    lambdas, fam_name, valid,
                    runs: tuple = (), partition=None) -> GLMModel:
        p: GLMParameters = self.params
        if p.solver.lower() in ("l_bfgs", "lbfgs"):
            return self._fit_lbfgs(job, frame, di, X, y, w, offset, n,
                                   penalize, lambdas[-1], fam_name, valid)
        fam = _make_family(fam_name, p)
        step = _make_irls_step(fam)
        P = di.nfeatures
        beta = np.zeros(P, dtype=np.float64)
        if di.add_intercept:
            with obs.span("glm.path", part="init"):
                eta0 = fam.init_eta(y, w)
                beta[-1] = float(eta0[0])      # a fetch: waits for the device
        if getattr(self, "_nonneg", None) is None:
            # every fit (single lambda included) runs as one fused device
            # program — the host loop below pays a device->host round trip
            # per IRLS iteration.  The host loop remains only for
            # non_negative (per-coordinate projection).
            # l1_mode only when L1 is actually active: the CD sweep costs
            # a while_loop per IRLS step that a plain solve doesn't.
            from ..runtime import failure
            failure.maybe_inject("glm_lambda")
            with obs.span("glm.path", lambdas=len(lambdas)):
                l1_mode = p.alpha > 0 and float(np.max(lambdas)) > 0
                if isinstance(X, CodedDesign):
                    layout = di.coded_layout()
                    runner = _make_blocked_path_runner(
                        fam, l1_mode, p.max_iterations, layout,
                        _fit_block_rows(layout, y.shape[0]), runs=runs)
                    design = tuple(X)
                    kernel = "pallas" if glm_gram.engages(layout) else "xla"
                else:
                    runner = _make_path_runner(fam, l1_mode=l1_mode,
                                               max_iter=p.max_iterations)
                    design = (X,)
                    kernel = "xla"
                # what forms the path's Grams: the kernel or XLA's product
                obs.inc("glm_gram_kernel_total", kernel=kernel)
                # RuleFit's lasso: its runs' partition flags, and the sweeps
                # of its coordinate descent come back sixth
                grouped = l1_mode and bool(runs)
                out = runner(
                    *design, y, w, offset, jnp.asarray(lambdas, jnp.float32),
                    jnp.float32(p.alpha), jnp.asarray(penalize, jnp.float32),
                    jnp.asarray(beta, jnp.float32), jnp.float32(n),
                    jnp.float32(p.beta_epsilon),
                    *((jnp.asarray(partition, bool),) if grouped else ()))
            with obs.span("glm.wait"):
                # the wait for the device and the fetch of its few KB in
                # one call, as device_get queues the copies behind the
                # program
                fetched = jax.device_get(out)
                obs.inc("transfer_bytes_total",
                        sum(a.nbytes for a in fetched), dir="d2h")
            betas, devs, iters, gram_fin, dev_fin = fetched[:5]
            obs.inc("glm_path_launches_total")
            # every IRLS iteration of every lambda and the pass of the final
            # Gram
            obs.inc("glm_irls_passes_total", int(np.sum(iters)) + 1)
            if grouped:
                # the coordinate-descent sweeps of all the path's solves
                obs.inc("glm_cd_sweeps_total", int(fetched[5]))
            hist = [{"lambda": float(lam), "iteration": int(iters[li]),
                     "deviance": float(devs[li]), "delta": float("nan")}
                    for li, lam in enumerate(lambdas)]
            for li, lam in enumerate(lambdas):
                job.update((li + 1) / len(lambdas),
                           f"lambda={lam:.3g} dev={float(devs[li]):.4g}")
            model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
            self._finalize(model, di, np.asarray(betas[-1], np.float64),
                           fam_name, X, y, w, n, float(devs[-1]),
                           hist, lambdas[-1], frame, valid,
                           gram_last=np.asarray(gram_fin, np.float64))
            return model
        best = None
        hist = []
        dev = np.inf
        from ..runtime import failure, scheduler, snapshot
        for li, lam in enumerate(lambdas):
            # the host lambda loop journals its position: the in-progress
            # state (warm-start beta) is not a loadable model, so this is
            # a cursor-only progress record (bounded-rework accounting +
            # the /3/Recovery status view), throttled like full snapshots
            failure.maybe_inject("glm_lambda")
            # per-lambda device-lease yield: co-resident jobs interleave
            # here (the tree drivers yield at chunk boundaries)
            scheduler.DEVICE_LEASE.yield_turn()
            snapshot.progress(job, {"lambda_index": li,
                                    "lambda": float(lam)})
            for it in range(p.max_iterations):
                # one batched fetch per iteration (each separate fetch
                # would wait for the device again)
                gram, xtwz, dev_new = jax.device_get(step(
                    X, y, w, jnp.asarray(beta, dtype=jnp.float32), offset))
                gram = np.asarray(gram, np.float64)
                xtwz = np.asarray(xtwz, np.float64)
                new_beta = _solve_penalized(gram, xtwz, n, lam, p.alpha,
                                            beta, penalize,
                                            nonneg=getattr(self, "_nonneg",
                                                           None))
                delta = float(np.max(np.abs(new_beta - beta)))
                beta = new_beta
                dev_new = float(dev_new)
                hist.append({"lambda": lam, "iteration": it,
                             "deviance": dev_new, "delta": delta})
                job.update((li + it / p.max_iterations) / len(lambdas),
                           f"lambda={lam:.3g} iter={it} dev={dev_new:.4g}")
                if delta < p.beta_epsilon:
                    break
            dev = hist[-1]["deviance"]
            best = beta.copy()

        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        self._finalize(model, di, best, fam_name, X, y, w, n,
                       dev, hist, lambdas[-1], frame, valid,
                       gram_last=gram)
        return model

    # -------------------------------------------------------- multinomial
    def _fit_multinomial(self, job, frame, di, X, y, w, offset, n, penalize,
                         lambdas, valid) -> GLMModel:
        p: GLMParameters = self.params
        K = di.nclasses
        P = di.nfeatures
        stats = _make_softmax_stats(K)
        beta = np.zeros((P, K), dtype=np.float64)
        hist = []
        lam = lambdas[-1]
        ll_prev = np.inf
        from ..runtime import failure, scheduler, snapshot
        for it in range(p.max_iterations):
            failure.maybe_inject("glm_lambda")
            scheduler.DEVICE_LEASE.yield_turn()
            snapshot.progress(job, {"iteration": it})
            # batched fetch of the SMALL outputs only — [:3] keeps the
            # [N, K] probs (4th return) on device
            grams, xtwz, ll = jax.device_get(stats(
                X, y, w, jnp.asarray(beta, jnp.float32), offset)[:3])
            grams = np.asarray(grams, np.float64)
            xtwz = np.asarray(xtwz, np.float64)
            delta = 0.0
            for k in range(K):
                bk = _solve_penalized(grams[k], xtwz[:, k], n, lam, p.alpha,
                                      beta[:, k], penalize)
                delta = max(delta, float(np.max(np.abs(bk - beta[:, k]))))
                beta[:, k] = bk
            ll = float(ll)
            hist.append({"lambda": lam, "iteration": it, "logloss": ll / n,
                         "delta": delta})
            job.update(it / p.max_iterations, f"iter={it} ll={ll:.4g}")
            if delta < p.beta_epsilon or abs(ll_prev - ll) < 1e-8 * n:
                break
            ll_prev = ll
        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        self._finalize(model, di, beta, "multinomial", X, y, w, n,
                       2 * ll, hist, lam, frame, valid)
        return model

    # ------------------------------------------------------------ finalize
    @obs.span("glm.finalize")       # a span is a decorator too: one per call
    def _finalize(self, model, di, beta_std, fam_name, X, y, w, n,
                  deviance, hist, lam, frame, valid, gram_last=None):
        p: GLMParameters = self.params
        # de-standardize coefficients back to the original data scale
        means = np.zeros(di.nfeatures)
        sigmas = np.ones(di.nfeatures)
        i = 0
        for s in di.specs:
            if s.type == "cat":
                i += s.width
            else:
                if di.standardize:
                    means[i], sigmas[i] = s.mean, s.sigma
                i += 1
        b = np.asarray(beta_std, np.float64)
        multi = b.ndim == 2
        bo = b / sigmas[:, None] if multi else b / sigmas
        if di.add_intercept:
            bo[-1] = b[-1] - (means[:-1] / sigmas[:-1]) @ b[:-1]

        model.output.update({
            "family": fam_name, "beta_std": np.asarray(beta_std, np.float32),
            "beta_std_flat": b.ravel().tolist(), "beta": bo,
            "coef_names": di.coef_names, "lambda": lam, "alpha": p.alpha,
            "iterations": len(hist), "residual_deviance": float(deviance),
            "rank": int(np.count_nonzero(np.atleast_2d(b))) ,
        })
        # null deviance
        fam = _make_family(fam_name if fam_name != "multinomial" else "binomial", p)
        if fam_name != "multinomial":
            mu0 = fam.linkinv(fam.init_eta(y, w))
            model.output["null_deviance"] = float(fam.deviance(y, mu0, w))
        model.scoring_history = hist
        # p-values for unpenalized fits (GLM.java compute_p_values path)
        if p.compute_p_values and lam == 0.0 and not multi and gram_last is not None:
            try:
                # a column no row lights (the NA column of a categorical
                # without NAs) is a zero row of the Gram: no standard error
                lit = np.diag(gram_last) > 0
                inv = np.linalg.inv(gram_last[np.ix_(lit, lit)])
                disp = (deviance / max(n - len(b), 1.0)
                        if fam_name in ("gaussian", "gamma", "tweedie") else 1.0)
                se = np.full(len(b), np.nan)
                se[lit] = np.sqrt(np.maximum(np.diag(inv) * disp, 0.0))
                zval = np.where(se > 0, b / np.maximum(se, 1e-30), np.nan)
                from scipy.stats import norm  # pragma: no cover
                pval = 2 * (1 - norm.cdf(np.abs(zval)))
            except Exception:
                se = zval = pval = None
            if se is not None:
                model.output.update({"std_errs": se, "z_values": zval,
                                     "p_values": pval})
        if gram_last is not None and not multi:
            model.output["gram"] = gram_last     # X'WX at the final beta
        # training + validation metrics, on the design the solver took
        raw = model._predict_raw(X)
        model.training_metrics = make_metrics(di, raw, y, w)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
