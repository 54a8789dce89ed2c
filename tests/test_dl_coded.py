"""DeepLearning on the code-form design (categoricals as codes): the design
against ``make_matrix``, the system's step and scoring against the plain
reference (``models/reference_dl.py``) on seeded weights, blocked scoring,
every activation and dropout mode, and the block sampler's parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT
from h2o3_tpu.models import reference_dl as ref
from h2o3_tpu.models import deeplearning as dl
from h2o3_tpu.models.datainfo import DataInfo, expand_coded
from h2o3_tpu.models.deeplearning import DeepLearning, DeepLearningModel

DOMAINS = {"c5": list("abcde"), "c9": [f"L{i}" for i in range(9)],
           "y": ["no", "yes"]}


def _frame(seed, n=600, domains=DOMAINS, na=True):
    """Two numerics and two categoricals around each other, NA in both
    kinds, a binary label that depends on all four."""
    rng = np.random.default_rng(seed)
    cols = {"x0": rng.normal(size=n).astype(np.float32),
            "c5": rng.integers(0, len(domains["c5"]), n).astype(np.int32),
            "x1": rng.normal(2.0, 3.0, size=n).astype(np.float32),
            "c9": rng.integers(0, len(domains["c9"]), n).astype(np.int32)}
    logit = cols["x0"] + 0.3 * (cols["c5"] % 2) - 0.2 * (cols["c9"] % 3) \
        + 0.1 * (cols["x1"] - 2.0)
    cols["y"] = (logit + 0.3 * rng.normal(size=n) > 0).astype(np.int32)
    if na:
        cols["x0"][::41] = np.nan
        cols["c5"][::29] = -1
        cols["c9"][::53] = -1
    return Frame.from_numpy(cols, types={k: T_CAT for k in domains},
                            domains=domains)


def _unseen_frame(seed, n=200):
    """A scoring frame whose ``c5`` has another order and a level the
    training frame never had."""
    domains = dict(DOMAINS, c5=["e", "zz", "a", "c", "b", "d"])
    return _frame(seed, n, domains)


def _seeded_model(fr, hidden=(12, 10), activation="rectifier", seed=0,
                  **params) -> DeepLearningModel:
    """A model at seeded random weights, no training."""
    builder = DeepLearning(response_column=None if params.get("autoencoder")
                           else "y", hidden=list(hidden),
                           activation=activation, precision="f32",
                           ignored_columns=["y"] if params.get("autoencoder")
                           else [], **params)
    di = builder._make_datainfo(fr)
    cfg = dl._step_config(builder.params, di)
    units = [h * (2 if activation.startswith("maxout") else 1) for h in hidden]
    sizes = [di.nfeatures, *units, cfg.out_dim]
    fan_in = [di.nfeatures, *hidden]
    rng = np.random.default_rng(seed)
    model = DeepLearningModel(f"dl_seeded_{seed}_{activation}",
                              builder.params, di)
    model.output["weights"] = [
        (rng.normal(0, 0.4, (i, o)).astype(np.float32),
         rng.normal(0, 0.1, o).astype(np.float32))
        for i, o in zip(fan_in, sizes[1:])]
    return model


def _dense(model, fr):
    n = fr.nrows
    di = model.datainfo
    return (np.asarray(di.make_matrix(fr))[:n],
            np.asarray(di.response(fr))[:n] if di.response_column else None,
            np.asarray(di.weights(fr))[:n])


def _layers(model):
    return [(jnp.asarray(W), jnp.asarray(b)) for W, b in model.output["weights"]]


def _first_step(model, fr, seed=0):
    """(loss, gradients) of the timed program's first step, ``fr`` one
    minibatch (the model's ``mini_batch_size`` is its row count, so the
    block at any offset of the wraparound copy holds every row once).  The
    gradients are read from ADADELTA's first step from zero accumulators:
    E[g^2] = (1 - rho) g^2, and the update has the gradient's sign reversed."""
    assert model.params.mini_batch_size == fr.nrows
    got = model.train_interval(fr, steps=1, seed=seed)
    scale = 1.0 - model.params.rho
    grads = [tuple(np.sign(before - after) * np.sqrt(e_g / scale)
                   for before, after, e_g in zip(b4, af, eg))
             for b4, af, eg in zip(model.output["weights"], got["weights"],
                                   got["accumulators"]["e_g"])]
    return got["loss"], grads


# ------------------------------------------------------------------ design
@pytest.mark.parametrize("standardize", [True, False])
@pytest.mark.parametrize("all_levels", [False, True])
@pytest.mark.parametrize("which", ["train", "unseen"])
def test_coded_design_expands_to_make_matrix(cl, standardize, all_levels, which):
    fr = _frame(1)
    di = DataInfo.fit(fr, response_column="y", standardize=standardize,
                      use_all_factor_levels=all_levels)
    on = fr if which == "train" else _unseen_frame(2)
    design = di.make_coded(on)
    dense = np.asarray(di.make_matrix(on))
    layout = di.coded_layout()
    assert sum(width for _, width in layout) == di.nfeatures == dense.shape[1]
    assert len(di.coef_names) == di.nfeatures
    assert design.num.shape == (on.padded_rows, 2)
    assert design.codes.shape == (on.padded_rows, 2)
    assert design.codes.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(expand_coded(layout, *design)), dense)
    # the NA level is each block's last column, an unseen level lands there
    c5 = next(s for s in di.specs if s.name == "c5")
    codes = np.asarray(design.codes)[: on.nrows, 0]
    raw = np.asarray(on.vec("c5").to_numpy())
    assert (codes[::29] == c5.width - 1).all()
    if which == "unseen":
        unseen = raw == on.vec("c5").domain.index("zz")
        assert unseen.any() and (codes[unseen] == c5.width - 1).all()


def test_coded_design_is_memoized_and_spilled(cl):
    fr = _frame(3)
    di = DataInfo.fit(fr, response_column="y")
    first = di.make_coded(fr)
    again = di.make_coded(fr)
    assert first.num is again.num and first.codes is again.codes
    held = first.num.nbytes + first.codes.nbytes
    assert fr.spill() >= held
    assert not fr._matrix_cache


def test_first_layer_is_the_sum_of_its_blocks(cl):
    """x W1[num] + sum_j W1[o_j + c_j] + W1[intercept] == onehot(x, c) W1."""
    fr = _frame(4)
    model = _seeded_model(fr)
    di = model.datainfo
    W1 = model.output["weights"][0][0].astype(np.float64)
    design = di.make_coded(fr)
    num = np.asarray(design.num, np.float64)[: fr.nrows]
    codes = np.asarray(design.codes)[: fr.nrows]
    total = np.zeros((fr.nrows, W1.shape[1]))
    at, i_num, i_cat = 0, 0, 0
    for kind, width in di.coded_layout():
        if kind == "num":
            total += num[:, i_num:i_num + width] @ W1[at:at + width]
            i_num += width
        elif kind == "cat":
            c = codes[:, i_cat]
            total += np.where((c >= 0)[:, None], W1[at + np.maximum(c, 0)], 0.0)
            i_cat += 1
        else:
            total += W1[at]
        at += width
    dense = np.asarray(di.make_matrix(fr), np.float64)[: fr.nrows]
    np.testing.assert_allclose(total, dense @ W1, rtol=0, atol=1e-12)


# ------------------------------------------------- the step, the reference
@pytest.mark.parametrize("activation", ["rectifier", "tanh", "maxout"])
def test_step_equals_reference(cl, activation):
    """Loss, gradients and three ADADELTA steps on one minibatch (NA levels
    in it) at seeded weights, float32."""
    fr = _frame(5, n=256)
    model = _seeded_model(fr, activation=activation, seed=7,
                          mini_batch_size=fr.nrows)
    X, y, w = _dense(model, fr)
    loss, grads = _first_step(model, fr)
    want_loss, want_grads = ref.loss_and_gradients(
        _layers(model), X, y, w, activation=activation)
    assert loss == pytest.approx(float(want_loss), rel=2e-6)
    for (gW, gb), (wW, wb) in zip(grads, want_grads):
        np.testing.assert_allclose(gW, wW, rtol=0, atol=2e-6)
        np.testing.assert_allclose(gb, wb, rtol=0, atol=2e-6)
    every = np.arange(fr.nrows)
    want_layers, _ = ref.fit(_layers(model), X, y, w, [every] * 3,
                             activation=activation)
    got = model.train_interval(fr, steps=3)
    for (gW, gb), (wW, wb) in zip(got["weights"], want_layers):
        # an update is of order 1e-3 a step where the gradient is not tiny
        np.testing.assert_allclose(gW, wW, rtol=0, atol=2e-5)
        np.testing.assert_allclose(gb, wb, rtol=0, atol=2e-5)


@pytest.mark.parametrize("shuffle", [True, False])
def test_interval_replays_against_reference(cl, shuffle):
    """A launch of the timed program at a minibatch smaller than the frame:
    the sampler's copy is the frame's dense rows permuted (numerics, codes,
    label and weight moved together) with the wraparound rows behind them,
    and the weights after k steps are the reference's over the k blocks at
    the offsets the launch drew."""
    fr = _frame(9, n=200)
    batch, k = 16, 6
    model = _seeded_model(fr, seed=13, mini_batch_size=batch,
                          shuffle_training_data=shuffle)
    got = model.train_interval(fr, steps=k, seed=4)
    n = fr.nrows
    copy = np.column_stack([got["rows"], got["labels"], got["row_weights"]])
    assert copy.shape[0] == n + batch
    np.testing.assert_array_equal(copy[n:], copy[:batch])
    own = np.column_stack(_dense(model, fr))
    if not shuffle:
        np.testing.assert_array_equal(copy[:n], own)
    np.testing.assert_array_equal(copy[np.lexsort(copy[:n].T)],
                                  own[np.lexsort(own.T)])
    offsets = got["offsets"]
    assert offsets.shape == (k,) and (0 <= offsets).all() and (offsets < n).all()
    blocks = [off + np.arange(batch) for off in offsets]
    want, losses = ref.fit(_layers(model), jnp.asarray(got["rows"]),
                           jnp.asarray(got["labels"]),
                           jnp.asarray(got["row_weights"]), blocks)
    assert got["loss"] == pytest.approx(float(np.mean(losses)), rel=2e-6)
    for (gW, gb), (wW, wb) in zip(got["weights"], want):
        np.testing.assert_allclose(gW, wW, rtol=0, atol=2e-5)
        np.testing.assert_allclose(gb, wb, rtol=0, atol=2e-5)


@pytest.mark.parametrize("kind", ["regression", "autoencoder", "l1l2"])
def test_step_equals_reference_other_losses(cl, kind):
    fr = _frame(6, n=256)
    if kind == "autoencoder":
        model = _seeded_model(fr, autoencoder=True, mini_batch_size=fr.nrows)
        X, _, w = _dense(model, fr)
        y, args = np.zeros(len(X), np.float32), {"kind": "autoencoder"}
    elif kind == "l1l2":
        model = _seeded_model(fr, l1=1e-3, l2=1e-2, mini_batch_size=fr.nrows)
        X, y, w = _dense(model, fr)
        args = {"l1": 1e-3, "l2": 1e-2}
    else:
        cols = {n: np.asarray(fr.vec(n).to_numpy()) for n in ("x0", "x1")}
        cols["c5"] = np.asarray(fr.vec("c5").to_numpy()).astype(np.int32)
        cols["y"] = (np.nan_to_num(cols["x0"]) * 2 + cols["c5"]).astype(np.float32)
        fr = Frame.from_numpy(cols, types={"c5": T_CAT},
                              domains={"c5": DOMAINS["c5"]})
        model = _seeded_model(fr, mini_batch_size=fr.nrows)
        di = model.datainfo
        X, y, w = _dense(model, fr)
        y, args = (y - di.response_mean) / di.response_sigma, {"kind": "quadratic"}
    loss, grads = _first_step(model, fr)
    want_loss, want_grads = ref.loss_and_gradients(_layers(model), X, y, w, **args)
    assert loss == pytest.approx(float(want_loss), rel=5e-6)
    for (gW, gb), (wW, wb) in zip(grads, want_grads):
        np.testing.assert_allclose(gW, wW, rtol=0, atol=5e-6)
        np.testing.assert_allclose(gb, wb, rtol=0, atol=5e-6)


def test_train_runs_the_reference_steps(cl):
    """The whole ``train()`` path with one minibatch of all rows a step
    (every offset of the wraparound copy then holds every row): k steps
    from a checkpoint's weights equal the reference's k steps."""
    fr = _frame(8, n=128, na=True)
    start = _seeded_model(fr, seed=11)
    k = 4
    model = DeepLearning(response_column="y", hidden=[12, 10], precision="f32",
                         checkpoint=start.key, mini_batch_size=fr.nrows,
                         train_samples_per_iteration=fr.nrows, epochs=k,
                         stopping_rounds=0, seed=3).train(fr)
    assert model.output["samples_trained"] == k * fr.nrows
    X, y, w = _dense(model, fr)
    every = np.arange(fr.nrows)
    want, losses = ref.fit(_layers(start), X, y, w, [every] * k)
    for (gW, gb), (wW, wb) in zip(model.output["weights"], want):
        np.testing.assert_allclose(gW, wW, rtol=0, atol=3e-5)
        np.testing.assert_allclose(gb, wb, rtol=0, atol=3e-5)
    # each iteration is one step here, so the history is the steps' losses
    got = [h["training_loss"] for h in model.scoring_history]
    np.testing.assert_allclose(got, [float(l) for l in losses], rtol=1e-5)


@pytest.mark.parametrize("activation", ["rectifier", "tanh", "maxout"])
def test_predict_equals_reference_with_unseen_level(cl, activation):
    model = _seeded_model(_frame(9), activation=activation, seed=5)
    on = _unseen_frame(10)
    want = np.asarray(ref.predict(
        _layers(model), np.asarray(model.datainfo.make_matrix(on))[: on.nrows],
        activation))
    got = model.predict(on)
    assert got.names == ["predict", "no", "yes"]
    np.testing.assert_allclose(got.vec("yes").to_numpy(), want[:, 1],
                               rtol=0, atol=2e-6)


# ---------------------------------------------------------------- scoring
@pytest.mark.parametrize("emit", ["softmax", "logits", "first", "anomaly"])
def test_blocked_scoring_equals_one_call(cl, emit):
    fr = _frame(12, n=1000)
    model = _seeded_model(fr, autoencoder=(emit == "anomaly"))
    design = model._score_matrix(fr)
    layout = model.datainfo.coded_layout()
    params = model._device_params()
    rows = design.num.shape[0] // cl.n_row_shards
    whole = dl._make_score(layout, "rectifier", emit, rows)(params, *design)
    for block in (32, 50):          # 50 does not divide a shard's rows
        assert rows % block or block == 32
        got = dl._make_score(layout, "rectifier", emit, block)(params, *design)
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                                   rtol=0, atol=1e-6)


def test_score_block_rows_follow_widths_and_rows(cl):
    narrow = dl._score_block_rows([628, 200, 200, 2], 10 ** 9)
    wide = dl._score_block_rows([628, 2000, 2000, 2], 10 ** 9)
    assert 1024 <= wide < narrow and narrow % 1024 == 0
    # a block and its activations stay inside a sixteenth of the device
    assert narrow * 2 * 4 * 1030 <= (4 << 30) // 16
    assert dl._score_block_rows([628, 200, 200, 2], 500) == 500


# ------------------------------------------ activations, dropout, autoenc
@pytest.mark.parametrize("params", [
    {"activation": "tanh"}, {"activation": "maxout"},
    {"activation": "rectifier_with_dropout"},
    {"activation": "tanh_with_dropout", "hidden_dropout_ratios": [0.2, 0.1]},
    {"activation": "maxout_with_dropout"},
    {"activation": "rectifier", "input_dropout_ratio": 0.2},
    {"activation": "rectifier", "precision": "bf16"},
], ids=lambda p: "-".join(str(v) for v in p.values()))
def test_every_mode_trains_through_the_coded_first_layer(cl, params):
    fr = _frame(13, n=800)
    params = {"precision": "f32", **params}
    m = DeepLearning(response_column="y", hidden=[16, 8], epochs=8, seed=3,
                     stopping_rounds=0, **params).train(fr)
    assert m.output["weights"][0][0].shape[0] == m.datainfo.nfeatures
    assert np.isfinite(m.training_metrics.logloss)
    assert m.training_metrics.auc > 0.6, m.training_metrics.describe()
    # scoring is the deterministic pass of the reference on the dense rows
    want = np.asarray(ref.predict(
        _layers(m), np.asarray(m.datainfo.make_matrix(fr))[: fr.nrows],
        params["activation"]))
    np.testing.assert_allclose(m.predict(fr).vec("yes").to_numpy(), want[:, 1],
                               rtol=0, atol=5e-6)


def test_autoencoder_reconstructs_the_expanded_row(cl):
    fr = _frame(14, n=500)
    m = DeepLearning(autoencoder=True, hidden=[6], epochs=5, seed=1,
                     stopping_rounds=0, ignored_columns=["y"]).train(fr)
    di = m.datainfo
    recon = m.predict(fr)
    assert recon.names == [f"reconstr_{c}" for c in di.coef_names]
    dense = np.asarray(di.make_matrix(fr))[: fr.nrows]
    logits = np.asarray(ref.forward(_layers(m), dense))
    np.testing.assert_allclose(
        m.anomaly(fr).vec("Reconstruction.MSE").to_numpy(),
        ((logits - dense) ** 2).mean(axis=1), rtol=1e-4, atol=1e-6)


# ------------------------------------------------------- the block sampler
@pytest.mark.parametrize("n,batch", [(96, 16), (101, 7), (64, 64)])
def test_block_sampler_includes_every_row_equally(cl, n, batch):
    """Over the offsets the sampler draws from, [0, n), every row lies in
    exactly ``batch`` blocks of the wraparound copy, shuffled or not."""
    ids = jnp.arange(n + 3, dtype=jnp.float32)      # 3 rows of padding
    for shuffle in (False, True):
        table, codes = dl._sample_copy_fn(n, batch, shuffle)(
            ids[:, None], ids[:, None].astype(jnp.int32), ids, -ids,
            jax.random.PRNGKey(1))
        assert table.shape == (n + batch, 3) and codes.shape == (n + batch, 1)
        rows = np.asarray(codes)[:, 0]
        assert sorted(rows[:n]) == list(range(n))           # a permutation
        np.testing.assert_array_equal(rows[n:], rows[:batch])  # wraparound
        # numerics, label and weight moved by the same index as the codes
        np.testing.assert_array_equal(
            np.asarray(table), np.stack([rows, rows, -rows], axis=1))
        counts = np.zeros(n, int)
        for off in range(n):
            counts[rows[off:off + batch]] += 1
        assert (counts == batch).all()


def test_block_sampler_loss_inside_band_of_per_row_sampling(cl):
    """After the same number of samples from the same weights, the loss
    over all rows of block sampling (the system) lies within 10 % of per-row
    sampling's (the reference, independent draws), and both well under the
    start's. The band is wide against the seed-to-seed scatter of either
    (about 2 %) and narrow against the start-to-end fall (a factor > 2)."""
    fr = _frame(15, n=2048, na=False)
    start = _seeded_model(fr, hidden=(16,), seed=21)
    batch, steps = 32, 256
    model = DeepLearning(response_column="y", hidden=[16], precision="f32",
                         checkpoint=start.key, mini_batch_size=batch,
                         train_samples_per_iteration=batch * steps,
                         epochs=batch * steps / fr.nrows, stopping_rounds=0,
                         seed=5).train(fr)
    assert model.output["samples_trained"] == batch * steps
    X, y, w = (jnp.asarray(a) for a in _dense(model, fr))

    @jax.jit
    def per_row(layers, key):
        def step(carry, k):
            layers, state = carry
            rows = jax.random.randint(k, (batch,), 0, fr.nrows)
            _, grads = ref.loss_and_gradients(layers, X[rows], y[rows], w[rows])
            return ref.adadelta_step(layers, grads, state), None
        carry = (layers, ref.adadelta_init(layers))
        return jax.lax.scan(step, carry, jax.random.split(key, steps))[0][0]

    def loss_of(layers):
        return float(ref.loss_and_gradients(layers, X, y, w)[0])

    at_start = loss_of(_layers(start))
    by_block = loss_of(_layers(model))
    by_row = loss_of(per_row(_layers(start), jax.random.PRNGKey(2)))
    assert by_row < 0.5 * at_start and by_block < 0.5 * at_start
    assert abs(by_block - by_row) <= 0.10 * by_row, (by_block, by_row, at_start)
