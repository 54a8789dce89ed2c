"""ANOVA GLM: Type-III sum-of-squares significance per predictor.

Reference: ``hex/anovaglm/ANOVAGLM.java`` — for each predictor, compare the
full GLM against the GLM with that predictor removed; the deviance
difference over its degrees of freedom gives the F statistic (gaussian)
or the likelihood-ratio chi-square (other families), with p-values from
the corresponding distribution.

TPU-native redesign: the leave-one-out refits reuse the device-resident
design columns; each fit is the standard jit-compiled IRLSM.  Pure host
control flow around compiled programs — same shape as ModelSelection.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from ..frame.frame import Frame
from ..runtime import dkv
from ..runtime.job import Job
from .base import Model, ModelBuilder, Parameters
from .glm import GLM


@dataclasses.dataclass
class ANOVAGLMParameters(Parameters):
    family: str = "auto"
    alpha: float = 0.0
    lambda_: float = 0.0


class ANOVAGLMModel(Model):
    algo = "anovaglm"

    def result(self) -> Frame:
        rows = self.output["anova_table"]
        return Frame.from_numpy({
            "predictor": np.asarray([r["predictor"] for r in rows],
                                    dtype=object),
            "df": np.asarray([r["df"] for r in rows], np.float64),
            "sum_of_squares": np.asarray([r["ss"] for r in rows],
                                         np.float64),
            "mean_square": np.asarray([r["ms"] for r in rows], np.float64),
            "f_value": np.asarray([r["f"] for r in rows], np.float64),
            "p_value": np.asarray([r["p"] for r in rows], np.float64),
        })

    def _score_matrix(self, frame: Frame):
        return dkv.get(self.output["full_model"])._score_matrix(frame)

    def _predict_raw(self, X):
        return dkv.get(self.output["full_model"])._predict_raw(X)


class ANOVAGLM(ModelBuilder):
    algo = "anovaglm"
    model_class = ANOVAGLMModel

    def __init__(self, params: Optional[ANOVAGLMParameters] = None, **kw):
        super().__init__(params or ANOVAGLMParameters(**kw))

    def _fit(self, job: Job, frame: Frame, di, valid) -> ANOVAGLMModel:
        from scipy import stats as sstats
        p: ANOVAGLMParameters = self.params
        predictors = [s.name for s in di.specs]
        extra = [p.response_column] + ([p.weights_column]
                                       if p.weights_column else [])

        def fit(cols: List[str]):
            return GLM(response_column=p.response_column,
                       weights_column=p.weights_column,
                       family=p.family, alpha=p.alpha, lambda_=p.lambda_,
                       seed=p.effective_seed()).train(frame[cols + extra])

        full = fit(predictors)
        gaussian = not full.datainfo.is_classifier and \
            full.output.get("family", "gaussian") == "gaussian"
        n_obs = frame.nrows
        # residual deviance of the full model = SSE for gaussian
        dev_full = full.output["residual_deviance"]
        df_model_full = sum(s.width if s.type == "cat" else 1
                            for s in full.datainfo.specs)
        df_resid = max(n_obs - df_model_full - 1, 1)
        rows = []
        for i, name in enumerate(predictors):
            reduced = fit([c for c in predictors if c != name])
            dev_red = reduced.output["residual_deviance"]
            spec = next(s for s in di.specs if s.name == name)
            df = float(max(spec.width - 1, 1)) if spec.type == "cat" \
                else 1.0
            ss = max(dev_red - dev_full, 0.0)
            ms = ss / df
            if gaussian:
                f = ms / max(dev_full / df_resid, 1e-300)
                pv = float(sstats.f.sf(f, df, df_resid))
            else:
                # likelihood-ratio chi-square for non-gaussian families
                f = ss / df
                pv = float(sstats.chi2.sf(ss, df))
            rows.append({"predictor": name, "df": df, "ss": ss, "ms": ms,
                         "f": f, "p": pv})
            job.update((i + 1) / len(predictors), name)

        model = ANOVAGLMModel(job.dest_key or dkv.make_key(self.algo),
                              p, di)
        model.output["anova_table"] = rows
        model.output["full_model"] = full.key
        model.training_metrics = full.training_metrics
        return model
