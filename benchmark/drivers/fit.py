"""Traffic ``fit``: whole ``Estimator(**params).train(frame)`` calls through
the public API, back to back, each ended when the model's device outputs are
ready. ``fit_s`` is the window's wall time over the fits it completed."""

from benchmark.drivers import _common

ANNOTATION = "bench.fit"


def set_up(cfg, mix, seed, data):
    state = _common.data_state(seed, data)
    state["cfg"] = cfg
    return state


def unit(state):
    model = _common.estimator(state["cfg"], state).train(state["frame"])
    _common.model_ready(model)
    return model


def metrics(state, units, elapsed):
    return {"fit_s": elapsed / units}
