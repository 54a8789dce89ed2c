"""Traffic ``score``: ``model.predict(frame)`` over every row of the frame,
back to back, each ended when the result frame is on the device.
``rows_per_s`` is rows scored over the window's wall time.

The model is trained in set-up with the mix's ``ntrees`` on the first
``train_rows`` rows: what scoring costs depends on the ensemble's shape, not
on what it was trained on, and training that many trees on the whole frame
would take minutes."""

from benchmark.drivers import _common

ANNOTATION = "bench.predict"


def set_up(cfg, mix, seed, data):
    state = _common.data_state(seed, data)
    head = {k: v[:mix["train_rows"]] for k, v in state["cols"].items()}
    builder = _common.estimator(cfg, state, ntrees=mix["ntrees"])
    model = builder.train(state["make_frame"](head))
    _common.model_ready(model)
    state.update(model=model, ntrees=mix["ntrees"],
                 depth=model.output["effective_max_depth"])
    return state


def unit(state):
    predictions = state["model"].predict(state["frame"])
    _common.sync_frame(predictions)
    return predictions


def metrics(state, units, elapsed):
    return {"rows_per_s": state["rows"] * units / elapsed}
