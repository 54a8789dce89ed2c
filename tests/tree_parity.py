"""Estimator-level parity of a tree kernel knob: two fits on the same frame
through both public values of one knob, compared tree by tree.

The contract is the one the in-training cross-check modes held before they
were deleted: which nodes split agrees exactly, how they split agrees
wherever they do (a node that does not split keeps a candidate record that
nothing reads, and the two paths may leave different ones there),
thresholds and leaf values agree to float32 tolerance.  ``bitwise=True`` is
for the pairs that promise the same bits (``tree_program`` off the TPU).
Each pair's cases live in the file of that pair, so that ``--dist loadfile``
spreads them.
"""

import jax
import numpy as np

from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT
from h2o3_tpu.models import DRF, GBM, UpliftDRF

MODELS = ("gbm_binomial", "gbm_3class", "drf", "uplift")
KW = dict(ntrees=3, max_depth=3, nbins=16, min_rows=2, seed=11,
          reproducible=True)


def _frame(model, n=400):
    r = np.random.default_rng(5)
    dist = np.abs(r.normal(700, 500, n))
    dist[r.random(n) < 0.1] = np.nan                  # an NA bucket
    dep = r.integers(0, 2400, n).astype(np.float64)
    carrier = r.integers(0, 7, n)
    cols = {"dep": dep, "dist": dist, "carrier": carrier}
    logit = (0.002 * (dep / 100 - 12) ** 2 - 0.0005 * np.nan_to_num(dist)
             / 100 + 0.3 * (carrier == 2) + 0.1 * r.normal(size=n))
    if model == "gbm_3class":
        y3 = np.digitize(logit, np.quantile(logit, [0.33, 0.66]))
        cols["y"] = np.array(["A", "B", "C"], dtype=object)[y3]
    elif model == "uplift":
        treat = r.integers(0, 2, n)
        p = 1 / (1 + np.exp(-(logit + 0.8 * treat * (dep > 1200))))
        cols["treatment"] = treat.astype(np.float64)
        cols["y"] = np.where(r.random(n) < p, "yes", "no").astype(object)
    else:
        yes = r.random(n) < 1 / (1 + np.exp(-logit))
        cols["y"] = np.where(yes, "YES", "NO").astype(object)
    return Frame.from_numpy(cols, types={"carrier": T_CAT},
                            domains={"carrier": [str(i) for i in range(7)]})


def _fit(model, fr, **kw):
    kw = {**KW, "response_column": "y", **kw}
    if model == "uplift":
        return UpliftDRF(treatment_column="treatment", **kw).train(fr)
    return (DRF if model == "drf" else GBM)(**kw).train(fr)


def _ensembles(m):
    """Host copies of every stacked ensemble a model holds: one for a
    single-class model, K class ensembles, or uplift's two arms."""
    out = m.output
    st = [out["stacked_pt"], out["stacked_pc"]] if "stacked_pt" in out \
        else out["stacked"]
    st = st if isinstance(st, (list, tuple)) else [st]
    return jax.device_get([(list(map(tuple, s.levels)), s.values)
                           for s in st])


def assert_same_trees(m_a, m_b, bitwise=False):
    ens_a, ens_b = _ensembles(m_a), _ensembles(m_b)
    assert len(ens_a) == len(ens_b)
    for k, ((lv_a, v_a), (lv_b, v_b)) in enumerate(zip(ens_a, ens_b)):
        assert len(lv_a) == len(lv_b) and v_a.shape == v_b.shape
        for d, (a, b) in enumerate(zip(lv_a, lv_b)):
            at = f"ensemble {k} level {d}"
            valid = np.asarray(a[3], bool)
            np.testing.assert_array_equal(valid, np.asarray(b[3], bool),
                                          err_msg=f"valid, {at}")
            for name, i in (("feat", 0), ("na_left", 2)):
                np.testing.assert_array_equal(
                    np.asarray(a[i])[valid], np.asarray(b[i])[valid],
                    err_msg=f"{name}, {at}")
            thr_a, thr_b = np.asarray(a[1])[valid], np.asarray(b[1])[valid]
            if bitwise:
                np.testing.assert_array_equal(thr_a, thr_b,
                                              err_msg=f"thr, {at}")
            else:
                np.testing.assert_allclose(thr_a, thr_b, atol=1e-4,
                                           rtol=1e-5, err_msg=f"thr, {at}")
        if bitwise:
            np.testing.assert_array_equal(v_a, v_b)
        else:
            np.testing.assert_allclose(v_a, v_b, atol=1e-4, rtol=1e-4)


def check_pair(model, knob, pair, bitwise=False, **kw):
    """Fit ``model`` with ``knob`` at both values of ``pair``; same trees."""
    fr = _frame(model)
    fits = [_fit(model, fr, **{knob: value}, **kw) for value in pair]
    assert_same_trees(*fits, bitwise=bitwise)
    return fits
