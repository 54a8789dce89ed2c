"""RuleFit (rules and linear terms under a binomial lasso) held to three
things and two controls, in float64 numpy from the host columns and the
model's own split tables, with no code of the system.

The rules: every node at the rule depths of every tree of the model's
forest (``model.forest()``: per depth feature, threshold, NA-left and
splits-or-not, [trees, 2^d]), walked from the root as a tree scores a row:
right where the row's value is at least the threshold (NaN: where NA does not
go left) and only at a node that splits.  A rule whose condition list (in
the path's order) equals an earlier one's is dropped; an unsplit node's
right child is the rule of no rows.  The design: each kept rule's raw 0/1
column, each numeric standardised by its float64 mean and sample deviation,
the intercept.  The lasso at lambda (``model.output["lambda"]``) standardises
the rule columns too, so a rule's penalty is its deviation s_j on its raw
coefficient g_j (b_j = g_j s_j).

(i)   The codes: the kept rules must be the model's (``rules_differ``), and
      every row's code in every group (``model.rule_codes``, all rows of the
      timed frame) the node the walk puts it at, or -1 where that node's rule
      was dropped: ``codes_wrong`` counts the entries that differ.
(ii)  The lasso's optimality conditions at the model's coefficients, over ALL
      rows: the gradient of the mean log-likelihood by each standardised
      coefficient, ``z_j'(y - mu) / n`` (rules by ``bincount`` over the
      reference's codes, numerics by dot products), is 0 for the intercept,
      ``lambda sign(b_j)`` for an active coefficient and at most lambda in
      size for the rest; ``kkt_max_abs`` is the largest violation.  And the
      model's lambda is the path's last: ``LAMBDA_MIN_RATIO`` of lambda_max,
      the largest of those gradients at the mean response, in float64
      (``lambda_rel``, the relative distance), so that a path stopped early
      fails however well it met the conditions at the lambda it reports.
      The forest's generator is the configuration's: a DRF of its trees and
      depth at H2O-3's shipped row sample and column count
      (``generator_wrong``, the settings that differ).
(iii) ``predict`` on the timed frame, every row, against the float64
      sigmoid of the model's coefficients on the reference's design:
      ``p1_max_abs``.
(iv)  Two controls in every run, each through the same verdict, each of
      which has to FAIL.  ``bf16``: the coefficients each moved by the
      relative error of one bfloat16 rounding (``BF16_REL``, sign from the
      seed), through (ii) and (iii).  ``tail``: the last hundredth of the
      rows' codes moved to the next node and their predictions left at 0,
      through (i) and (iii).
"""

import numpy as np

from benchmark import refs

BF16_REL = 4e-3             # 2^-8: the rounding of a bfloat16 mantissa
LAMBDA_MIN_RATIO = 1e-4     # the path's last lambda over its first (GLM's default)
DRF_DEFAULTS = {"sample_rate": 0.632, "mtries": -1}     # H2O-3's DRF
LIMITS = ("codes_wrong", "kkt_max_abs", "p1_max_abs", "lambda_rel",
          "generator_wrong")


def conditions(levels, t, d, nid):
    """The root path of tree ``t``'s node ``nid`` at depth ``d``: ((feature,
    right, threshold, NA in), ...), None where it holds no row."""
    conds = []
    for e in range(d):
        parent, right = nid >> (d - e), (nid >> (d - e - 1)) & 1
        feat, thr, na_left, valid = (np.asarray(a)[t, parent] for a in levels[e])
        if not valid:
            if right:
                return None
            continue
        conds.append((int(feat), int(right), float(thr), bool(na_left) != bool(right)))
    return tuple(conds)


def kept_rules(levels, lo):
    """(tree, depth, node) of every rule at depths lo..D, less duplicates."""
    seen, rules = set(), []
    trees = np.asarray(levels[0][0]).shape[0]
    for t in range(trees):
        for d in range(lo, len(levels) + 1):
            for nid in range(2 ** d):
                c = conditions(levels, t, d, nid)
                if c not in seen:
                    seen.add(c)
                    rules.append((t, d, nid))
    return rules


def walk(levels, columns, t):
    """[depth + 1] arrays of every row's node id in tree ``t`` (int16): at
    each node every row's decision by the node's own split, kept where the
    row is at that node (whole-column passes, no compaction)."""
    rows = len(columns[0])
    node = np.zeros(rows, np.int16)
    path = [node]
    for feat, thr, na_left, valid in levels:
        feat, thr, na_left, valid = (np.asarray(a)[t] for a in (feat, thr, na_left, valid))
        right = np.zeros(rows, bool)
        for k in np.flatnonzero(valid):
            x = columns[int(feat[k])]
            here = np.where(np.isnan(x), not na_left[k], x >= thr[k])
            np.copyto(right, here, where=node == k)
        node = (2 * node + right).astype(np.int16)
        path.append(node)
    return path


class Design:
    """The reference's rule codes and standardised numerics."""

    def __init__(self, state, model):
        cols = state["cols"]
        self.features = state["features"]
        self.y = cols[state["response"]].astype(np.float64)
        self.n = len(self.y)
        self.x = [cols[f] for f in self.features]
        self.stats = [(float(np.mean(x, dtype=np.float64)),
                       float(np.std(x, dtype=np.float64, ddof=1))) for x in self.x]
        levels = model.forest()
        remap = np.asarray(model.output["rule_remap"])
        self.depths = remap.shape[1]
        self.lo = len(levels) - self.depths + 1
        self.rules = kept_rules(levels, self.lo)
        kept = {(t, d): [] for t, d, _ in self.rules}
        for t, d, k in self.rules:
            kept[t, d].append(k)
        self.groups = []            # (tree, depth) of each group of codes
        self.codes = []             # [rows] int16: the node, -1 where dropped
        self.counts = []            # rows at each level of the group
        trees = np.asarray(levels[0][0]).shape[0]
        for t in range(trees):
            path = walk(levels, self.x, t)
            for d in range(self.lo, len(levels) + 1):
                table = np.full(2 ** d, -1, np.int16)
                table[kept.get((t, d), [])] = kept.get((t, d), [])
                self.groups.append((t, d))
                self.codes.append(table[path[d]])
                self.counts.append(np.bincount(self.codes[-1] + 1,
                                               minlength=2 ** d + 1)[1:])

    def eta(self, coef):
        """The linear predictor of the coefficients (``model.coef``: raw rule
        columns, numerics on the original scale, the intercept)."""
        eta = np.full(self.n, float(coef["Intercept"]))
        for f, x in zip(self.features, self.x):
            eta += float(coef[f]) * x.astype(np.float64)
        for (t, d), codes in zip(self.groups, self.codes):
            table = np.array([0.0] + [float(coef[f"T{t}D{d}.N{k}"]) for k in range(2 ** d)])
            eta += table[codes + 1]
        return eta

    def lambda_max(self):
        """The least lambda at which every penalised coefficient is 0: the
        largest gradient by a standardised column at the mean response."""
        grads, _ = self.gradients(None, np.full(self.n, self.y.mean()))
        return float(np.max(np.abs(grads)))

    def kkt(self, coef, mu, lam):
        """The largest violation of the standardised lasso's optimality
        conditions at ``coef`` whose probabilities are ``mu``."""
        grads, betas = self.gradients(coef, mu)
        worst = [abs((self.y - mu).sum()) / self.n]
        on = betas != 0
        worst.append(np.max(np.abs(grads[on] - lam * np.sign(betas[on])), initial=0.0))
        worst.append(np.max(np.abs(grads[~on]) - lam, initial=0.0))
        return float(max(worst))

    def gradients(self, coef, mu):
        """The gradient of the mean log-likelihood by every penalised
        standardised coefficient at probabilities ``mu``, and those
        coefficients (``coef``'s, or zeros)."""
        resid = self.y - mu
        total = resid.sum()
        grads, betas = [], []
        for f, x, (m, s) in zip(self.features, self.x, self.stats):
            grads.append((resid @ x.astype(np.float64) - m * total) / (self.n * s))
            betas.append(float(coef[f]) * s if coef else 0.0)
        for (t, d), codes, count in zip(self.groups, self.codes, self.counts):
            width = 2 ** d
            part = np.bincount(codes + 1, resid, minlength=width + 1)[1:]
            for k in np.flatnonzero(count):
                p = count[k] / self.n
                s = np.sqrt(p * (1 - p) * self.n / (self.n - 1)) or 1.0
                grads.append((part[k] - p * total) / (self.n * s))
                betas.append(float(coef[f"T{t}D{d}.N{k}"]) * s if coef else 0.0)
        return np.array(grads), np.array(betas)


def verdict(read, tol):
    """The limits of ``tol`` that ``read`` is over, or has no reading for."""
    return [k for k in LIMITS if not read.get(k, float("inf")) <= tol[k]]


def generator_wrong(state, model):
    """The generator's settings that are not the configuration's DRF."""
    params = state["cfg"]["params"]
    gen = model.generator()
    want = dict(DRF_DEFAULTS, ntrees=params["rule_generation_ntrees"],
                max_depth=params["max_rule_length"])
    wrong = [k for k, v in want.items() if getattr(gen.params, k) != v]
    return wrong + ([] if gen.algo == "drf" else ["algo"])


def check(state, model, tol):
    if state["categorical"]:
        return False, {"layout": "the reference reads numeric linear terms only"}
    coef = model.coef
    if not np.all(np.isfinite(list(coef.values()))):
        return False, {"coef": "not finite"}
    design = Design(state, model)
    lam = float(model.output["lambda"])
    lam_last = design.lambda_max() * LAMBDA_MIN_RATIO
    settings = generator_wrong(state, model)

    # (i) the kept rules and every row's code in every group
    got = np.ascontiguousarray(np.asarray(model.rule_codes(state["frame"])).T)

    def codes_read(codes):
        if codes.shape != (len(design.codes), design.n):
            return {"codes_wrong": design.n * len(design.codes)}
        return {"codes_wrong": int(sum(np.count_nonzero(c != w)
                                       for c, w in zip(codes, design.codes)))}

    rules_differ = [tuple(r) for r in model.output["rules"]] != design.rules
    read = codes_read(got)
    read.update(lambda_rel=abs(lam / lam_last - 1.0), generator_wrong=len(settings))
    if rules_differ:
        read["codes_wrong"] = max(read["codes_wrong"], 1)

    # (iii) predict on the timed frame, through the public API
    label = state["domains"][state["response"]][1]
    frame_p1 = np.asarray(model.predict(state["frame"]).vec(label).to_numpy(), np.float64)

    def compare(coef):
        mu = refs.sigmoid(design.eta(coef))
        return {"kkt_max_abs": design.kkt(coef, mu, lam),
                "p1_max_abs": float(np.abs(frame_p1 - mu).max())}, mu

    more, mu = compare(coef)
    read.update(more)
    failed = verdict(read, tol)
    detail = dict(read, failed=failed, rules=len(design.rules), rules_differ=rules_differ,
                  groups=len(design.groups), active=int(sum(v != 0 for v in coef.values())),
                  generator=settings, lambda_last=lam_last, **{"lambda": lam})

    # (iv) the controls: coefficients a bfloat16 rounding away must fail ...
    sign = np.random.default_rng([state["seed"], 2]).choice((-1.0, 1.0), len(coef))
    control, _ = compare({k: v * (1.0 + BF16_REL * s)
                          for (k, v), s in zip(coef.items(), sign)})
    detail["bf16"] = control
    detail["bf16_fails"] = verdict(dict(read, **control), tol)
    # ... and so must a tail of rows whose codes and scores went wrong
    tail = max(design.n // 100, 1)
    moved = got.copy()
    moved[:, -tail:] = np.where(moved[:, -tail:] >= 0, (moved[:, -tail:] + 1) % 8, 0)
    lost = mu.copy()
    lost[-tail:] = 0.0
    wrong = dict(codes_read(moved), p1_max_abs=float(np.abs(frame_p1 - lost).max()))
    detail["tail"] = wrong
    detail["tail_fails"] = verdict(dict(read, **wrong), tol)
    return not failed, detail
