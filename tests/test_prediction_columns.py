"""``Model.predict``'s result frame is built by one device program
(``models/base.py:prediction_columns``) from the raw scores.  The host
construction it replaced is kept here as the reference: every column must
hold the same bits, padding included."""

import types

import jax
import numpy as np
import pytest

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import T_CAT, T_NUM, Vec
from h2o3_tpu.models.base import Model, prediction_columns


def _host_prediction_frame(model, raw):
    """``Model._prediction_frame`` as it was before the device program: host
    labels over the fetched scores, then an upload per result column."""
    di = model.datainfo
    if di.is_classifier:
        dom = di.response_domain
        labels = np.argmax(raw, axis=1)
        if raw.shape[1] == 2:
            thr = model.default_threshold()
            labels = (raw[:, 1] >= thr).astype(np.int64)
        names = ["predict"] + [str(d) for d in dom]
        vecs = [Vec.from_numpy(labels.astype(np.int32), T_CAT,
                               domain=[str(d) for d in dom])]
        vecs += [Vec.from_numpy(raw[:, k], T_NUM) for k in range(raw.shape[1])]
        return Frame(names, vecs)
    return Frame(["predict"], [Vec.from_numpy(raw.astype(np.float64), T_NUM)])


def _model(domain, threshold=None):
    """A ``Model`` as far as ``_prediction_frame`` reads one."""
    model = object.__new__(Model)
    model.datainfo = types.SimpleNamespace(
        is_classifier=domain is not None, response_domain=domain)
    model.training_metrics = None if threshold is None else \
        types.SimpleNamespace(max_f1_threshold=threshold)
    return model


MAX_F1 = 0.3137                     # not a float32: the compare must round it


def _binomial(rng, n, thr):
    p1 = rng.random(n).astype(np.float32)
    p1[:4] = [np.float32(thr), np.nextafter(np.float32(thr), np.float32(0)),
              np.nextafter(np.float32(thr), np.float32(1)), np.nan]
    return np.stack([1 - p1, p1], axis=1)


def _multinomial(rng, n):
    raw = rng.dirichlet(np.ones(3), n).astype(np.float32)
    raw[0] = [0.25, 0.5, 0.25]
    raw[1] = [0.4, 0.4, 0.2]        # a tie: the first maximum
    raw[2] = [0.2, 0.4, 0.4]
    raw[3] = [0.3, np.nan, 0.7]     # NaN counts as the largest
    raw[4] = [np.nan, np.nan, np.nan]
    return raw


KINDS = {
    "binomial_half": lambda rng, n: (_model([0, 1]), _binomial(rng, n, 0.5)),
    "binomial_max_f1": lambda rng, n: (_model(["no", "yes"], MAX_F1),
                                       _binomial(rng, n, MAX_F1)),
    "multinomial": lambda rng, n: (_model(["a", "b", "c"]), _multinomial(rng, n)),
    "regression": lambda rng, n: (_model(None),
                                  rng.normal(size=n).astype(np.float32)),
}
# rows of the frame, rows of the raw scores; the suite's mesh pads to 64
GEOMETRY = {"raw_is_padded": (300, 320), "raw_longer": (300, 512),
            "raw_shorter": (300, 304), "whole_multiple": (128, 128)}


@pytest.mark.parametrize("geometry", GEOMETRY)
@pytest.mark.parametrize("kind", KINDS)
def test_the_device_built_frame_equals_the_host_built_one(cl, kind, geometry):
    nrows, nraw = GEOMETRY[geometry]
    padded = cl.pad_rows(nrows)
    assert geometry == "whole_multiple" or nrows % cl.row_multiple()
    model, raw = KINDS[kind](np.random.default_rng(35), nraw)
    sharding = cl.row_sharding if raw.ndim == 1 else cl.matrix_sharding
    got = model._prediction_frame(jax.device_put(raw, sharding), nrows)
    want = _host_prediction_frame(model, raw[:nrows])
    assert got.names == want.names and got.nrows == want.nrows == nrows
    for name, g, w in zip(got.names, got.vecs, want.vecs):
        assert (g.type, g.nrows, g.domain) == (w.type, nrows, w.domain), name
        assert g.data.sharding == cl.row_sharding, name
        assert g.data.dtype == w.data.dtype and g.data.shape == (padded,), name
        gb, wb = np.asarray(g.data), np.asarray(w.data)
        np.testing.assert_array_equal(gb.view(np.int32), wb.view(np.int32), name)
        tail = gb[nrows:]
        assert (tail == -1).all() if g.type == T_CAT else np.isnan(tail).all()
        np.testing.assert_array_equal(g.to_numpy(), w.to_numpy(), name)
    label = got.vecs[0]
    assert label.type == (T_NUM if kind == "regression" else T_CAT)
    if kind.startswith("binomial"):
        # at the threshold 1, one float32 under it 0, over it 1, NaN 0
        assert label.to_numpy()[:4].tolist() == [1, 0, 1, 0]
    if kind == "multinomial":
        assert label.to_numpy()[:5].tolist() == [1, 0, 1, 1, 0]


def test_a_threshold_or_a_row_count_within_one_padding_compiles_nothing(cl):
    raw = jax.device_put(_binomial(np.random.default_rng(1), 192, 0.5),
                         cl.matrix_sharding)
    _model([0, 1])._prediction_frame(raw, 190)
    compiled = prediction_columns._cache_size()
    for model, nrows in [(_model([0, 1], MAX_F1), 190), (_model([0, 1], 0.9), 131)]:
        frame = model._prediction_frame(raw, nrows)
        want = np.asarray(raw)[:nrows, 1] >= np.float32(model.default_threshold())
        np.testing.assert_array_equal(frame.vecs[0].to_numpy(), want.astype(np.int32))
        assert np.asarray(frame.vecs[1].data)[nrows:].view(np.int32).tolist() \
            == np.full(192 - nrows, np.nan, np.float32).view(np.int32).tolist()
    assert prediction_columns._cache_size() == compiled
