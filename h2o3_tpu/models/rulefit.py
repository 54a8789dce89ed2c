"""RuleFit: tree-ensemble rules + linear terms under an L1 GLM.

Reference: ``hex/rulefit/RuleFit.java`` — fit a small tree ensemble, convert
every node's root path into a binary rule feature, optionally append the
linear terms, then fit a lasso GLM over [rules, linear].

TPU-native redesign: the rules at one depth of one tree partition the rows
(a row is in exactly one node there), so they are ONE categorical of 2^d
levels, and a row's level is its node id at that depth.  ``jit_rule_codes``
walks every tree of the generator at once and writes those ids, [rows,
trees x depths] int32, on the device; the GLM reads them as its design in
code form (``datainfo.CodedDesign``), beside the linear terms, a block of
rows at a time (``GLM.fit_coded``).  The rule matrix [rows, rules] is never
built, on the host or on the device.  A rule whose condition list equals an
earlier one's (``remove_duplicates``) lights no column: its level's code is
-1, which no one-hot column matches.

The objective is the GLM's lasso (``alpha=1``) over the 0/1 rule columns
r_j and the linear terms, each numeric standardised as GLM's
``standardize=True`` does it:

    min  -(1/n) sum_i w_i loglik(y_i, b0 + sum_j b_j (x_ij - m_j) / s_j)
         + lambda sum_j |b_j|

with m_j and s_j a column's mean and sample deviation (s_j = 1 where it is
0).  On the raw 0/1 rule columns, g_j = b_j / s_j and an intercept that
takes -sum_j g_j m_j, it is the same problem with the penalty factor s_j
on |g_j|; that is what the code form holds: a rule group's levels are raw
one-hot columns (``ColumnSpec.all_levels``) with penalty factors s_j from
the group's counts, which ``jit_rule_codes`` returns beside the codes, and
the numerics are GLM's standardised ones with factor 1.  The lambda path,
lambda_max included, is taken on these factors (``GLM._lambda_path``).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..runtime import dkv
from ..runtime import observability as obs
from ..runtime.cluster import cluster, put_sharded
from ..runtime.job import Job
from .base import Model, ModelBuilder, Parameters
from .datainfo import CodedDesign, DataInfo


@dataclasses.dataclass
class RuleFitParameters(Parameters):
    algorithm: str = "gbm"               # rule generator: gbm | drf (auto)
    min_rule_length: int = 1
    max_rule_length: int = 3
    max_num_rules: int = -1              # -1: auto
    model_type: str = "rules_and_linear"  # rules | linear | rules_and_linear
    rule_generation_ntrees: int = 30
    remove_duplicates: bool = True
    lambda_: Optional[float] = None


def _pick(node: jax.Array, table: jax.Array) -> jax.Array:
    """``table[node]`` for every row, by one select per entry of the small
    table: no gather, every value exact."""
    out = jnp.full(node.shape, table[0], table.dtype)
    for k in range(1, table.shape[0]):
        out = jnp.where(node == k, table[k], out)
    return out


def rule_codes(cols, levels, remap, nrows):
    """Every row's rule codes and every rule's count of rows.

    ``cols`` the generator's design columns, [padded] each (numerics as
    they are, categoricals as codes, NA as NaN); ``levels`` its stacked
    trees down to the deepest rule, per depth d (feat, thr, na_left, valid)
    [trees, 2^d]; ``remap`` [trees, rule depths, 2^D] the code of each node
    at each rule depth (its id, or -1 where its rule lights no column).  A
    row goes right where its feature is at least the threshold (NaN: where
    NA does not go left) and the node splits at all, as ``traverse``
    walks.  Returns codes [padded, trees x rule depths] int32, group g the
    (g // depths)-th tree at the (g % depths)-th rule depth, and counts
    [groups, 2^D] int32 over the first ``nrows`` rows."""
    n = cols[0].shape[0]
    depths = remap.shape[1]
    lo = len(levels) - depths           # rule depths lo + 1 .. D

    def one_tree(_, tree):
        lv, rm = tree
        node = jnp.zeros(n, jnp.int32)
        out = []
        for d, (feat, thr, na_left, valid) in enumerate(lv):
            f = _pick(node, feat)
            x = cols[0]
            for fi in range(1, len(cols)):
                x = jnp.where(f == fi, cols[fi], x)
            right = jnp.where(jnp.isnan(x), ~_pick(node, na_left),
                              x >= _pick(node, thr)) & _pick(node, valid)
            node = 2 * node + right.astype(jnp.int32)
            if d >= lo:
                out.append(_pick(node, rm[d - lo, :2 ** (d + 1)]))
        return None, jnp.stack(out)

    _, codes = jax.lax.scan(one_tree, None, (tuple(levels), remap))
    real = jnp.arange(n) < nrows
    width = remap.shape[2]
    counts = jnp.sum((codes[..., None] == jnp.arange(width, dtype=jnp.int32))
                     & real[:, None], axis=2, dtype=jnp.int32)
    return (codes.reshape(-1, n).T,
            counts.reshape(-1, width))


# the name the device trace knows the program by
jit_rule_codes = jax.jit(rule_codes)


def _conditions(levels, t: int, d: int, nid: int):
    """The root path of tree ``t``'s node ``nid`` at depth ``d`` as its
    condition list, ((feature, right, threshold, NA in), ...) in the path's
    order; a node that does not split sends every row left, so its left
    child adds no condition and its right child holds no row (None)."""
    conds = []
    for e in range(d):
        parent, right = nid >> (d - e), (nid >> (d - e - 1)) & 1
        feat, thr, na_left, valid = (lv[t, parent] for lv in levels[e])
        if not valid:
            if right:
                return None
            continue
        conds.append((int(feat), int(right), float(thr),
                      bool(na_left) != bool(right)))
    return tuple(conds)


def _describe(conds, di: DataInfo) -> str:
    """A condition list as the rule's text."""
    if conds is None:
        return "(no rows)"
    parts = []
    for feat, right, thr, _ in conds:
        name = di.specs[feat].name if feat < len(di.specs) else f"f{feat}"
        parts.append(f"{name} {'>=' if right else '<'} {thr:.6g}")
    return " & ".join(parts)


class RuleFitModel(Model):
    algo = "rulefit"

    def _rule_codes(self, frame: Frame):
        """``jit_rule_codes`` over ``frame`` with this model's forest:
        (codes [padded, groups], counts [groups, levels])."""
        gen = self.generator()
        levels = gen.output["stacked"].levels[:self.output["rule_max_depth"]]
        codes, counts = jit_rule_codes(
            tuple(gen._design_columns(frame)), [tuple(lv) for lv in levels],
            jnp.asarray(self.output["rule_remap"]), np.int32(frame.nrows))
        return put_sharded(codes, cluster().matrix_sharding), counts

    def generator(self) -> Model:
        """The tree model the rules were read from."""
        return dkv.get(self.output["rule_model_key"])

    def forest(self) -> list:
        """The generator's split tables on the host, down to the deepest
        rule: per depth d (feature, threshold, NA left, splits) [trees,
        2^d]."""
        return jax.device_get([tuple(lv) for lv in self.generator().output[
            "stacked"].levels[:self.output["rule_max_depth"]]])

    def rule_codes(self, frame: Frame) -> jax.Array:
        """``frame``'s rule codes [rows, trees x rule depths], int32 on the
        device: a row's node id at each rule depth of each tree (group g is
        tree g // depths at the (g % depths)-th), -1 where the node's rule
        lights no column (a duplicate)."""
        return self._rule_codes(frame)[0][:frame.nrows]

    def _score_matrix(self, frame: Frame, codes=None) -> CodedDesign:
        """The rule design of ``frame`` in code form: the rule groups'
        codes (``codes`` where the caller has them), then the linear
        terms' (``make_coded``)."""
        parts, num = [], jnp.zeros((frame.padded_rows, 0), jnp.float32)
        if self.output["rule_remap"] is not None:
            parts.append(self._rule_codes(frame)[0] if codes is None
                         else codes)
        if self.params.model_type in ("linear", "rules_and_linear"):
            num, lin = self.datainfo.make_coded(frame)
            parts.append(lin)
        parts = [c for c in parts if c.shape[1]] or [
            jnp.zeros((frame.padded_rows, 0), jnp.int32)]
        return CodedDesign(num, parts[0] if len(parts) == 1
                           else jnp.concatenate(parts, axis=1))

    def _predict_raw(self, X: CodedDesign) -> jax.Array:
        return dkv.get(self.output["glm_key"])._predict_raw(X)

    @property
    def coef(self) -> dict:
        """The lasso's coefficients: a rule's on its raw 0/1 column (name
        ``T<tree>D<depth>.N<node>``), a linear term's on the original scale,
        the intercept."""
        return dkv.get(self.output["glm_key"]).coef

    def rule_importance(self) -> List[dict]:
        glm = dkv.get(self.output["glm_key"])
        names, beta = glm.output["coef_names"], glm.output["beta"]
        rule_of = {at: i for i, at in enumerate(self.output["rule_coef"])}
        out = []
        for at, (name, coef) in enumerate(zip(names, beta)):
            if abs(coef) <= 1e-10 or name == "Intercept":
                continue
            if at in rule_of:
                i = rule_of[at]
                out.append({"variable": f"rule_{i}", "coefficient": coef,
                            "rule": self.output["rule_descriptions"][i]})
            elif at >= self.output["n_rule_columns"]:
                out.append({"variable": f"linear_{name}",
                            "coefficient": coef})
        return sorted(out, key=lambda r: -abs(r["coefficient"]))


class RuleFit(ModelBuilder):
    """RuleFit builder — H2ORuleFitEstimator analog."""

    algo = "rulefit"
    model_class = RuleFitModel

    def __init__(self, params: Optional[RuleFitParameters] = None, **kw):
        super().__init__(params or RuleFitParameters(**kw))

    def _generator(self, depth: int):
        """The rule generator: DRF at its shipped defaults (H2O's ``auto``),
        or GBM at RuleFit's own rate and sample."""
        p: RuleFitParameters = self.params
        from .tree.gbm import GBM
        from .tree.drf import DRF
        common = dict(response_column=p.response_column,
                      ignored_columns=p.ignored_columns,
                      ntrees=p.rule_generation_ntrees, max_depth=depth,
                      seed=p.effective_seed())
        if p.algorithm.lower() in ("drf", "auto"):
            return DRF(**common)
        if p.algorithm.lower() == "gbm":
            return GBM(sample_rate=0.7, learn_rate=0.1, **common)
        raise ValueError(f"rulefit algorithm {p.algorithm!r}: gbm | drf | auto")

    def _enumerate(self, levels, di: DataInfo):
        """Every node at depths [min_rule_length, max_rule_length] of every
        tree, in order, less the duplicates of earlier ones and, past
        ``max_num_rules``, a seeded sample: (rules (tree, depth, node),
        descriptions, remap [trees, rule depths, 2^D], duplicates)."""
        p: RuleFitParameters = self.params
        D, T = len(levels), levels[0][0].shape[0]
        depths = range(max(p.min_rule_length, 1), D + 1)
        remap = np.full((T, len(depths), 2 ** D), -1, np.int32)
        seen, rules, conds, dupes = set(), [], [], 0
        for t in range(T):
            for k, d in enumerate(depths):
                for nid in range(2 ** d):
                    c = _conditions(levels, t, d, nid)
                    if p.remove_duplicates and c in seen:
                        dupes += 1
                        continue
                    seen.add(c)
                    rules.append((t, d, nid))
                    conds.append(c)
        if p.max_num_rules > 0 and len(rules) > p.max_num_rules:
            keep = sorted(np.random.default_rng(p.effective_seed()).choice(
                len(rules), p.max_num_rules, replace=False))
            rules, conds = [rules[i] for i in keep], [conds[i] for i in keep]
        for t, d, nid in rules:
            remap[t, d - depths[0], nid] = nid
        return rules, [_describe(c, di) for c in conds], remap, dupes

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> RuleFitModel:
        p: RuleFitParameters = self.params
        from .glm import GLM
        if di.is_classifier and di.nclasses > 2:
            raise ValueError("rulefit supports regression and binary "
                             "classification only (multinomial rule "
                             "generation not yet implemented)")
        linear = p.model_type in ("linear", "rules_and_linear")
        model = RuleFitModel(job.dest_key or dkv.make_key(self.algo), p, di)
        model.output.update(rule_remap=None, rules=[], rule_descriptions=[],
                            rule_coef=[])
        groups, factors, runs, partition, codes = [], [], [], [], None
        if p.model_type in ("rules", "rules_and_linear"):
            depth = max(p.max_rule_length, 1)
            job.update(0.1, "growing rule trees")
            with obs.span("rulefit.forest"):
                gen = self._generator(depth).train(frame)
                # the host's read of the trees: the forest's device work ends
                levels = jax.device_get(
                    [tuple(lv) for lv in gen.output["stacked"].levels])
            with obs.span("rulefit.rules"):
                rules, descr, remap, dupes = self._enumerate(levels, di)
                obs.inc("rulefit_rules_total", len(rules), kind="rule")
                obs.inc("rulefit_rules_total", dupes, kind="duplicate")
            model.output.update(rule_model_key=gen.key, rules=rules,
                                rule_descriptions=descr, rule_remap=remap,
                                rule_max_depth=len(levels))
            with obs.span("rulefit.codes"):
                codes, counts = model._rule_codes(frame)
                # the penalty factors' counts: waits for jit_rule_codes
                counts = np.asarray(jax.device_get(counts), np.float64)
            lo = len(levels) - remap.shape[1] + 1
            first = {}                  # (tree, depth) -> its first column
            for g in range(counts.shape[0]):
                t, k = divmod(g, remap.shape[1])
                d = lo + k
                first[t, d] = n_rules = sum(len(lv) for _, lv in groups)
                groups.append((f"T{t}D{d}", [f"N{i}" for i in range(2 ** d)]))
                share = counts[g, :2 ** d] / max(frame.nrows, 1)
                sd = np.sqrt(share * (1 - share)
                             * frame.nrows / max(frame.nrows - 1, 1))
                factors.append(np.where(sd > 0, sd, 1.0))
                runs.append((n_rules, 2 ** d))
                # every level lit: the group's rows are the frame's
                partition.append(bool((remap[t, k, :2 ** d] >= 0).all()))
            model.output["rule_coef"] = [first[t, d] + nid
                                         for t, d, nid in rules]
        n_rules = sum(len(lv) for _, lv in groups)
        model.output["n_rule_columns"] = n_rules
        obs.inc("rulefit_rules_total",
                di.nfeatures - int(di.add_intercept) if linear else 0,
                kind="linear")

        job.update(0.5, f"fitting the lasso over {len(model.output['rules'])}"
                        " rules")
        with obs.span("rulefit.glm"):
            rdi = di.with_groups(groups, keep_specs=linear)
            X = model._score_matrix(frame, codes)
            penalize = np.concatenate(
                factors + [np.ones(rdi.nfeatures - n_rules)])
            if rdi.add_intercept:
                penalize[-1] = 0.0
            y = jnp.nan_to_num(di.response(frame))
            w = di.weights(frame)
            offset = di.offsets(frame)
            glm = GLM(response_column=p.response_column, alpha=1.0,
                      lambda_=p.lambda_, lambda_search=p.lambda_ is None,
                      seed=p.effective_seed()).fit_coded(
                Job("rulefit lasso"), frame, rdi, X, y, w,
                offset if offset is not None else jnp.zeros_like(y), penalize,
                tuple(runs), np.asarray(partition, bool) & rdi.add_intercept)
        model.output["glm_key"] = glm.key
        model.output["lambda"] = glm.output["lambda"]
        model.training_metrics = glm.training_metrics
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model
