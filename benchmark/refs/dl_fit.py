"""A multi-layer perceptron fit for a binary label (H2O-3's DeepLearning:
rectifier layers, softmax cross-entropy, ADADELTA), held to its mathematics
in numpy float64 at three places. The layout the exported weights refer to is
rebuilt here from the host columns and checked against the model's own
``coef_names``: numerics standardised, then per categorical one column per
level but the first and one for NA, then the intercept's column of ones. The
standardisation constants are this file's own, float64 over all rows; the
model's must agree with them to ``standardise_rel`` (of a deviation).

(a) ``predict``. The model's class-1 probability on ``predict_rows`` seeded
rows (more than one of the scoring program's row blocks, and no multiple of
one, so that its block walk and its laid-back last block are in it) must
equal the forward pass of this file over the exported weights to
``p1_max_abs``.

(b) The step. ``model.train_interval`` launches the fit's own programs, the
ones the window timed, on ``step_rows`` seeded rows at the fitted weights
from a fresh optimizer state: ``jit_dl_sample_copy`` makes the sampler's
copy, ``jit_dl_train_steps`` runs minibatches of the fit's own size at
offsets it draws. What comes back is replayed here:
- the copy's rows must be this file's rows of the same frame in some order,
  numerics, one-hot blocks, label and weight moved together, followed by the
  wraparound rows: ``copy_max_abs``;
- a launch of one step: its loss against this file's on the rows at that
  offset (``loss_rel``) and its gradient, read from ADADELTA's first step
  (E[g^2] = (1 - rho) g^2, the update has the gradient's sign reversed)
  against this file's backward pass (``grad_rel``);
- a launch of ``step_count`` steps: the weights' change (``update_rel``) and
  both accumulators (``accum_rel``) against this file's ADADELTA over the same
  blocks, and the mean loss (in ``loss_rel``).
``step_launches`` of each are made, on other offsets. ``loss_rel`` and
``copy_max_abs`` are the largest any launch read. Each of the other three
is, for every array of the layers, the root of the summed squared 2-norms of
the differences over that of the norms they are relative to, pooled over the
launches, and of those the worst array's: a relu's mask flips wherever a
pre-activation lies within rounding of zero, a flipped unit moves a
minibatch's gradient by a whole term, and over a single minibatch of 128 rows
the error then swings ninefold from seed to seed (PERF.md section 6, PR 30).
The gradient's error is taken relative to the norm of |h|' |dz| (the sum of
the absolute values of the terms the gradient sums), not of the gradient: at
a fitted model a gradient is the small difference of large sums, and
rounding errors scale with the sums.

(c) What was learned. The AUC of ``predict`` on ``auc_sample_rows`` seeded
rows, ranked here against the labels, must lie inside ``auc_band``.

Controls. ``Twin`` is this reference in the program's place. ``check`` puts
two of them through the same comparison at the same limits and reports what
each read and which limits it failed: ``fp8`` (every matrix product's
operands rounded to a float8 mantissa, e4m3's 3 bits, the nearest precision
below the bf16 the configuration states) and ``half_minibatch`` (a step that
drops the second half of its rows). The limits lie between the program's
readings and a control's.
"""

import numpy as np

from benchmark import refs
from benchmark.refs import tree_fit

CONTROL_PREDICT_ROWS = 10_000       # of predict_rows, through the float8 twin
LIMITS = ("standardise_rel", "p1_max_abs", "copy_max_abs", "loss_rel",
          "grad_rel", "update_rel", "accum_rel")


def rounded(x, bits):
    """``x`` with its mantissa rounded to ``bits`` stored bits (None: as it
    is); exponent range and subnormals are not modelled."""
    if bits is None:
        return x
    m, e = np.frexp(x)
    return np.ldexp(np.round(m * 2.0 ** (bits + 1)) / 2.0 ** (bits + 1), e)


def standardisation(cols, features, categorical):
    """{numeric feature: (mean, deviation)} over all rows, in float64."""
    return {f: (float(np.mean(cols[f], dtype=np.float64)),
                float(np.std(cols[f], dtype=np.float64, ddof=1)))
            for f in features if f not in categorical}


def coef_names(features, categorical, domains):
    names = []
    for f in features:
        if f in categorical:
            names += [f"{f}.{level}" for level in domains[f][1:]]
            names.append(f"{f}.missing(NA)")
        else:
            names.append(f)
    return names + ["Intercept"]


def design(cols, rows, features, categorical, domains, stats):
    """Dense rows [len(rows), expanded width], float64."""
    blocks = []
    for f in features:
        x = cols[f][rows]
        if f in categorical:
            levels = len(domains[f])
            block = np.zeros((len(rows), levels))       # levels - 1, and NA
            lit = x >= 1
            block[np.flatnonzero(lit), x[lit] - 1] = 1.0
            block[x < 0, levels - 1] = 1.0
            blocks.append(block)
        else:
            mean, dev = stats[f]
            x = np.where(np.isnan(x), mean, x.astype(np.float64))
            blocks.append(((x - mean) / dev)[:, None])
    blocks.append(np.ones((len(rows), 1)))
    return np.concatenate(blocks, axis=1)


def forward(layers, X, bits=None):
    """(logits, inputs of every layer, pre-activations of the hidden ones)."""
    hs, zs = [X], []
    for W, b in layers[:-1]:
        zs.append(rounded(hs[-1], bits) @ rounded(W, bits) + b)
        hs.append(np.maximum(zs[-1], 0.0))
    W, b = layers[-1]
    return rounded(hs[-1], bits) @ rounded(W, bits) + b, hs, zs


def p1(layers, X, bits=None):
    logits = forward(layers, X, bits)[0]
    return refs.sigmoid(logits[:, 1] - logits[:, 0])    # softmax of two


def loss_and_gradients(layers, X, y, w, bits=None):
    """(loss, gradients, scales): the weighted mean softmax cross-entropy of
    the rows, its gradients by a backward pass written out, and beside each
    gradient the same sum over the absolute values of its terms. Every
    product's operands pass through ``rounded``."""
    logits, hs, zs = forward(layers, X, bits)
    logp = logits - np.logaddexp.reduce(logits, axis=1, keepdims=True)
    n = len(y)
    share = w / w.sum()
    loss = -(share * logp[np.arange(n), y]).sum()
    dz = np.exp(logp)
    dz[np.arange(n), y] -= 1.0
    dz *= share[:, None]
    grads, scales = [], []
    for l in range(len(layers) - 1, -1, -1):
        grads.append((rounded(hs[l], bits).T @ rounded(dz, bits), dz.sum(axis=0)))
        scales.append((np.abs(hs[l]).T @ np.abs(dz), np.abs(dz).sum(axis=0)))
        if l > 0:
            dz = (rounded(dz, bits) @ rounded(layers[l][0], bits).T) * (zs[l - 1] > 0)
    return loss, grads[::-1], scales[::-1]


def adadelta(layers, minibatches, rho, eps, bits=None, skip=()):
    """(weights, E[g^2], E[D^2], mean loss) after one ADADELTA step on each
    of ``minibatches`` (each an ``(X, y, w)``) from zero accumulators, the
    first three lists of ``(W, b)`` pairs. ``skip`` names an accumulator
    (``"e_d"``) that a faulty optimizer leaves at zero."""
    flat = [p.astype(np.float64) for layer in layers for p in layer]
    e_g = [np.zeros_like(p) for p in flat]
    e_d = [np.zeros_like(p) for p in flat]
    losses = []
    for X, y, w in minibatches:
        pairs = list(zip(flat[0::2], flat[1::2]))
        loss, grads, _ = loss_and_gradients(pairs, X, y, w, bits)
        losses.append(loss)
        for i, g in enumerate(g for pair in grads for g in pair):
            e_g[i] = rho * e_g[i] + (1 - rho) * g * g
            d = -np.sqrt(e_d[i] + eps) / np.sqrt(e_g[i] + eps) * g
            if "e_d" not in skip:
                e_d[i] = rho * e_d[i] + (1 - rho) * d * d
            flat[i] = flat[i] + d
    return tuple(list(zip(a[0::2], a[1::2])) for a in (flat, e_g, e_d)) + (
        float(np.mean(losses)),)


class Pooled:
    """Per array of a list of ``(W, b)`` pairs, the squared 2-norms of
    differences and of what they are relative to, summed over launches."""

    def __init__(self):
        self.off, self.over = {}, {}

    def add(self, got, want, scale=None):
        """``got`` against ``want``, relative to ``scale`` (``want`` where
        none is given)."""
        for i, (g_pair, w_pair) in enumerate(zip(got, want)):
            for j, (g, w) in enumerate(zip(g_pair, w_pair)):
                over = w if scale is None else scale[i][j]
                self.off[i, j] = self.off.get((i, j), 0.0) + float(
                    np.sum((np.asarray(g, np.float64) - w) ** 2))
                self.over[i, j] = self.over.get((i, j), 0.0) + float(np.sum(over ** 2))

    def worst(self):
        return max(float(np.sqrt(self.off[k] / self.over[k])) for k in self.off)


def change(after, before):
    return [(np.asarray(Wa, np.float64) - Wb, np.asarray(ba, np.float64) - bb)
            for (Wa, ba), (Wb, bb) in zip(after, before)]


def first_step_gradients(before, after, e_g, rho):
    """The gradient an ADADELTA step from zero accumulators was given:
    E[g^2] = (1 - rho) g^2, and the update has the gradient's sign reversed."""
    return [tuple(np.sign(np.asarray(b4, np.float64) - np.asarray(af, np.float64))
                  * np.sqrt(np.asarray(eg, np.float64) / (1.0 - rho))
                  for b4, af, eg in zip(*pairs))
            for pairs in zip(before, after, e_g)]


class Data:
    """What the references of this file read of the driver's state."""

    def __init__(self, state):
        self.state, self.cols = state, state["cols"]
        self.features, self.categorical = state["features"], state["categorical"]
        self.domains, self.y = state["domains"], state["cols"][state["response"]]
        self.stats = standardisation(self.cols, self.features, self.categorical)

    def sample(self, count, stream):
        """``count`` seeded row numbers of the frame, ascending."""
        rows = np.random.default_rng([self.state["seed"], stream]).choice(
            len(self.y), min(len(self.y), count), replace=False)
        rows.sort()
        return rows

    def dense(self, rows, stats=None):
        return design(self.cols, rows, self.features, self.categorical, self.domains,
                      stats or self.stats)

    def upload(self, rows):
        return self.state["make_frame"]({k: v[rows] for k, v in self.cols.items()})


class System:
    """The program under test, as ``check`` reads it."""

    def __init__(self, model, data):
        self.model, self.data = model, data
        self.layers = [(np.asarray(W, np.float64), np.asarray(b, np.float64))
                       for W, b in model.output["weights"]]
        self.coef_names = list(model.datainfo.coef_names)
        self.stats = {s.name: (float(s.mean), float(s.sigma))
                      for s in model.datainfo.specs if s.domain is None}
        p = model.params
        self.rho, self.eps, self.batch = p.rho, p.epsilon, p.mini_batch_size

    def p1(self, rows):
        return tree_fit.sample_p1(self.model, self.data.upload(rows), self.data.state)

    def prepare(self, rows):
        return self.data.upload(rows)

    def interval(self, frame, steps, seed):
        return self.model.train_interval(frame, steps=steps, seed=seed)


class Twin:
    """This reference in the program's place: a control. ``bits`` rounds the
    operands of every product; ``keep`` is how many of a minibatch's rows a
    step uses; ``skip`` an accumulator the optimizer leaves out; ``stats``
    other standardisation constants; ``blind`` columns of the expanded row
    that the first layer never reads; ``roll_codes`` shifts the one-hot
    blocks of the sampler's copy by one row against its numerics."""

    def __init__(self, program, data, bits=None, keep=None, skip=(), stats=None,
                 blind=(), roll_codes=False):
        self.data, self.bits, self.skip, self.roll_codes = data, bits, skip, roll_codes
        self.layers, self.coef_names = program.layers, program.coef_names
        self.stats = stats or data.stats
        self.rho, self.eps, self.batch = program.rho, program.eps, program.batch
        self.keep = keep or self.batch
        self.seen = np.ones(len(self.coef_names))
        self.seen[list(blind)] = 0.0

    def p1(self, rows):
        return p1(self.layers, self.data.dense(rows, self.stats) * self.seen, self.bits)

    def prepare(self, rows):
        return self.data.dense(rows, self.stats), self.data.y[rows]

    def interval(self, prepared, steps, seed):
        X, y = prepared
        n, batch = len(y), min(self.batch, len(y))
        rng = np.random.default_rng([seed, steps])
        order = rng.permutation(n)
        order = np.r_[order, order[:batch]]
        X, y, w = X[order], y[order], np.ones(len(order))
        if self.roll_codes:
            numeric = [i for i, f in enumerate(self.coef_names) if f in self.stats]
            moved = np.roll(X, 1, axis=0)
            moved[:, numeric] = X[:, numeric]
            X = moved
        offsets = rng.integers(0, n, steps)
        keep = min(self.keep, batch)
        blocks = [(X[o:o + keep] * self.seen, y[o:o + keep], w[o:o + keep]) for o in offsets]
        weights, e_g, e_d, loss = adadelta(self.layers, blocks, self.rho, self.eps,
                                           self.bits, self.skip)
        return {"offsets": offsets, "rows": X, "labels": y, "row_weights": w, "loss": loss,
                "weights": weights, "accumulators": {"e_g": e_g, "e_d": e_d}}


def replay(program, prepared, own, steps, seed):
    """One launch of ``program`` on the prepared rows and the blocks it read
    as this file has them (``own``: its dense rows, label and weight of the
    same rows): (what came back, the largest difference between its copy and
    ``own``, the minibatches)."""
    got = program.interval(prepared, steps, seed)
    n, batch = len(own), min(program.batch, len(own))
    copy = np.column_stack([got["rows"], got["labels"], got["row_weights"]]).astype(np.float64)
    if copy.shape != (n + batch, own.shape[1]):
        return got, float("inf"), None
    # both in one order: by the one-hot blocks and the label, which are
    # exact, before the numerics, which are monotone in the raw values
    numeric = [i for i, f in enumerate(program.coef_names) if f in program.stats]
    keys = numeric[::-1] + [i for i in range(own.shape[1]) if i not in numeric]
    at_got, at_own = np.lexsort(copy[:n, keys].T), np.lexsort(own[:, keys].T)
    off = max(float(np.abs(copy[at_got] - own[at_own]).max()),
              float(np.abs(copy[n:] - copy[:batch]).max()))
    source = np.empty(n, np.int64)          # row of ``own`` behind each row of the copy
    source[at_got] = at_own
    source = np.r_[source, source[:batch]]
    blocks = []
    for o in np.asarray(got["offsets"]):
        at = source[o:o + batch]
        blocks.append((own[at, :-2], own[at, -2].astype(np.int64), own[at, -1]))
    return got, off, blocks


def readings(program, data, tol, want_p1, auc=True, predict_rows=None):
    """Every number ``verdict`` compares, for ``program`` (the system or a
    control)."""
    detail = {}
    off = {f: max(abs(program.stats[f][0] - data.stats[f][0]),
                  abs(program.stats[f][1] - data.stats[f][1])) / data.stats[f][1]
           for f in data.stats}
    worst = max(off, key=off.get)
    detail.update(standardise_rel=off[worst], standardise_worst=worst)

    # (a) predict against the forward pass over the exported weights
    rows = data.sample(tol["predict_rows"], 1)[:predict_rows]
    detail["p1_max_abs"] = float(np.abs(program.p1(rows) - want_p1[:len(rows)]).max())

    # (b) the timed programs, replayed: launches of one step and of step_count
    layers, rows = program.layers, data.sample(tol["step_rows"], 2)
    prepared = program.prepare(rows)
    own = np.column_stack([data.dense(rows), data.y[rows], np.ones(len(rows))])
    grad, update, acc_g, acc_d = Pooled(), Pooled(), Pooled(), Pooled()
    copy_off, loss_off = 0.0, 0.0
    for launch in range(tol["step_launches"]):
        seed = data.state["seed"] + 2 * launch
        got, off_1, blocks = replay(program, prepared, own, 1, seed)
        if blocks is None or got.get("accumulators") is None:
            return dict(detail, interval="no copy of the rows, or no accumulators, came back")
        loss_1, grads, scales = loss_and_gradients(layers, *blocks[0])
        grad.add(first_step_gradients(layers, got["weights"], got["accumulators"]["e_g"],
                                      program.rho), grads, scales)
        loss_off = max(loss_off, abs(got["loss"] - loss_1) / loss_1)

        got, off_k, blocks = replay(program, prepared, own, tol["step_count"], seed + 1)
        if blocks is None:
            return dict(detail, interval="no copy of the rows came back")
        after, e_g, e_d, loss_k = adadelta(layers, blocks, program.rho, program.eps)
        update.add(change(got["weights"], layers), change(after, layers))
        acc_g.add(got["accumulators"]["e_g"], e_g)
        acc_d.add(got["accumulators"]["e_d"], e_d)
        loss_off = max(loss_off, abs(got["loss"] - loss_k) / loss_k)
        copy_off = max(copy_off, off_1, off_k)
    detail.update(copy_max_abs=copy_off, loss_rel=loss_off, grad_rel=grad.worst(),
                  update_rel=update.worst(), accum_rel=max(acc_g.worst(), acc_d.worst()))

    # (c) what was learned
    if auc:
        rows = data.sample(tol["auc_sample_rows"], 3)
        detail["auc"] = refs.auc(program.p1(rows), data.y[rows])
    return detail


def verdict(detail, tol):
    """The limits of ``tol`` that ``detail`` is over, or has no reading for."""
    failed = [k for k in LIMITS if not detail.get(k, float("inf")) <= tol[k]]
    if "auc" in detail and not tol["auc_band"][0] <= detail["auc"] <= tol["auc_band"][1]:
        failed.append("auc_band")
    return failed


def check(state, model, tol):
    """``model``: what the window's last unit returned, or a ``Twin`` in its
    place (a control, which must come out as not correct)."""
    data = Data(state)
    if isinstance(model, Twin):
        program = model
    else:
        program = System(model, data)
        if not all(np.isfinite(p).all() for layer in program.layers for p in layer):
            return False, {"weights": "not finite"}
    names = coef_names(data.features, data.categorical, data.domains)
    if program.coef_names != names or program.layers[0][0].shape[0] != len(names):
        return False, {"layout": "the model's coef_names are not the reference's"}

    rows = data.sample(tol["predict_rows"], 1)
    want_p1 = np.concatenate([p1(program.layers, data.dense(part))
                              for part in np.array_split(rows, len(rows) // 65_536 + 1)])
    detail = readings(program, data, tol, want_p1)
    failed = verdict(detail, tol)
    detail["failed"] = failed
    if not isinstance(model, Twin):
        # the controls: must fail a limit each; their p1 on a part of the rows
        for name, twin in (("fp8", Twin(program, data, bits=3)),
                           ("half_minibatch", Twin(program, data, keep=program.batch // 2))):
            read = readings(twin, data, tol, want_p1, auc=False,
                            predict_rows=CONTROL_PREDICT_ROWS)
            detail[name] = {k: read[k] for k in LIMITS if k in read}
            detail[name + "_fails"] = verdict(read, tol)
        detail["standardise_rel_none"] = max(       # constants (0, 1): not standardised
            max(abs(mean), abs(1.0 - dev)) / dev for mean, dev in data.stats.values())
    return not failed, detail
