"""Plain reference of DeepLearning's mathematics: the forward pass, the loss,
its gradients written out by hand, and the ADADELTA step, in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``, on
the DENSE one-hot expansion of the frame (``DataInfo.make_matrix``).  No
kernel, no row blocks, no sampler, no autodiff, no code of
``deeplearning.py``: the tests hold the system against this, and
``benchmark/refs/dl_fit.py`` is its numpy twin for the chip.

With ``X`` the expanded rows [B, P], layers ``(W_l, b_l)``, ``f`` the
activation, ``y`` the label and ``w`` the row weights:

    h_0 = X;  z_l = h_{l-1} W_l + b_l;  h_l = f(z_l);  logits = z_L
    loss = sum_i w_i per_i / sum_i w_i  (+ l1 sum|W| + l2 sum W^2)
    per_i = -log softmax(logits_i)[y_i]        (classification)
          = (logits_i0 - y_i)^2                 (regression, quadratic)
          = mean_j (logits_ij - X_ij)^2         (autoencoder)

ADADELTA per minibatch, for every weight and bias (Zeiler 2012, as H2O-3's
``adaptive_rate`` with ``rho``, ``epsilon``):

    E[g^2] <- rho E[g^2] + (1 - rho) g^2
    D      =  -sqrt(E[D^2] + eps) / sqrt(E[g^2] + eps) * g
    E[D^2] <- rho E[D^2] + (1 - rho) D^2;   w <- w + D

Departures from H2O-3 (``hex/deeplearning``), which the system shares:
minibatches are synchronous, one update from the mean gradient of the
minibatch, where H2O-3 runs Hogwild threads of single-row updates and
averages node models (SURVEY.md section 2.10); ``mini_batch_size`` defaults to
128, H2O-3's to 1; the system draws a minibatch as one random-offset block
of adjacent rows of a once-shuffled copy (this reference has no sampler: it
takes the minibatches it is given); maxout pairs adjacent units of one
product where H2O-3 keeps two weight matrices.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

Layers = List[Tuple[jax.Array, jax.Array]]


def _activate(activation: str, z):
    base = activation.replace("_with_dropout", "")
    if base == "rectifier":
        return jnp.maximum(z, 0.0)
    if base == "tanh":
        return jnp.tanh(z)
    if base == "maxout":
        return z.reshape(z.shape[0], -1, 2).max(axis=2)
    raise ValueError(f"unknown activation {activation!r}")


def _activate_back(activation: str, z, h, dh):
    """d loss / d z from d loss / d h."""
    base = activation.replace("_with_dropout", "")
    if base == "rectifier":
        return dh * (z > 0)
    if base == "tanh":
        return dh * (1.0 - h * h)
    pairs = z.reshape(z.shape[0], -1, 2)
    first = pairs[:, :, 0] >= pairs[:, :, 1]        # ties: the first unit
    return jnp.stack([dh * first, dh * ~first], axis=2).reshape(z.shape)


def forward(layers: Layers, X, activation: str = "rectifier"):
    """Logits [B, out] of the dense rows ``X``; no dropout (scoring)."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(X, jnp.float32)
        for W, b in layers[:-1]:
            h = _activate(activation, h @ W + b)
        W, b = layers[-1]
        return h @ W + b


def predict(layers: Layers, X, activation: str = "rectifier"):
    """Class probabilities [B, K]."""
    return jax.nn.softmax(forward(layers, X, activation), axis=1)


def loss_and_gradients(layers: Layers, X, y, w, activation: str = "rectifier",
                       kind: str = "cross_entropy", l1: float = 0.0,
                       l2: float = 0.0):
    """(loss, [(dW_l, db_l)]) of one minibatch, backward pass by hand.
    ``kind``: ``cross_entropy`` (``y`` class codes), ``quadratic`` (``y``
    values, one output) or ``autoencoder`` (the target is ``X``)."""
    with jax.default_matmul_precision("highest"):
        X = jnp.asarray(X, jnp.float32)
        w = jnp.asarray(w, jnp.float32)
        hs, zs = [X], []
        for W, b in layers[:-1]:
            zs.append(hs[-1] @ W + b)
            hs.append(_activate(activation, zs[-1]))
        W, b = layers[-1]
        logits = hs[-1] @ W + b
        wsum = jnp.maximum(w.sum(), 1e-12)
        if kind == "cross_entropy":
            yi = jnp.asarray(y).astype(jnp.int32)
            logp = logits - jax.scipy.special.logsumexp(logits, axis=1,
                                                        keepdims=True)
            per = -jnp.take_along_axis(logp, yi[:, None], axis=1)[:, 0]
            dlogits = jnp.exp(logp) - jax.nn.one_hot(yi, logits.shape[1])
        elif kind == "quadratic":
            err = logits[:, 0] - jnp.asarray(y, jnp.float32)
            per = err ** 2
            dlogits = (2.0 * err)[:, None]
        elif kind == "autoencoder":
            err = logits - X
            per = jnp.mean(err ** 2, axis=1)
            dlogits = 2.0 * err / X.shape[1]
        else:
            raise ValueError(f"unknown loss kind {kind!r}")
        loss = jnp.sum(per * w) / wsum
        dz = dlogits * (w / wsum)[:, None]
        grads = []
        for l in range(len(layers) - 1, -1, -1):
            W, _ = layers[l]
            grads.append((hs[l].T @ dz, dz.sum(axis=0)))
            if l > 0:
                dz = _activate_back(activation, zs[l - 1], hs[l], dz @ W.T)
        grads.reverse()
        if l1 > 0 or l2 > 0:
            loss = loss + sum(l2 * jnp.sum(W * W) + l1 * jnp.sum(jnp.abs(W))
                              for W, _ in layers)
            grads = [(dW + 2.0 * l2 * W + l1 * jnp.sign(W), db)
                     for (dW, db), (W, _) in zip(grads, layers)]
        return loss, grads


def adadelta_init(layers: Layers):
    """(E[g^2], E[D^2]) at zero, shaped like the layers."""
    zeros = [(jnp.zeros_like(W), jnp.zeros_like(b)) for W, b in layers]
    return zeros, zeros


def adadelta_step(layers: Layers, grads, state, rho: float = 0.99,
                  eps: float = 1e-8):
    """One ADADELTA update: (layers, state) after it."""
    e_g, e_d = state

    def one(p, g, eg, ed):
        eg = rho * eg + (1.0 - rho) * g * g
        d = -jnp.sqrt(ed + eps) / jnp.sqrt(eg + eps) * g
        ed = rho * ed + (1.0 - rho) * d * d
        return p + d, eg, ed

    new, new_g, new_d = [], [], []
    for (W, b), (dW, db), (gW, gb), (xW, xb) in zip(layers, grads, e_g, e_d):
        W, gW, xW = one(W, dW, gW, xW)
        b, gb, xb = one(b, db, gb, xb)
        new.append((W, b))
        new_g.append((gW, gb))
        new_d.append((xW, xb))
    return new, (new_g, new_d)


def fit(layers: Layers, X, y, w, minibatches: Sequence, **loss_args):
    """ADADELTA over the given minibatches (each an index array into the
    rows), one step each, from a zero state: (layers, [loss per step])."""
    rho = loss_args.pop("rho", 0.99)
    eps = loss_args.pop("eps", 1e-8)
    state = adadelta_init(layers)
    losses = []
    for rows in minibatches:
        loss, grads = loss_and_gradients(layers, X[rows], y[rows], w[rows],
                                         **loss_args)
        layers, state = adadelta_step(layers, grads, state, rho, eps)
        losses.append(loss)
    return layers, losses
