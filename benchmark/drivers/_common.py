"""What every driver does alike: data from the seed, the frame, readiness."""

import importlib

import jax


def load(kind, name):
    """The module ``benchmark/<kind>/<name>.py``."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def resolve(dotted):
    """``package.module.Name`` -> the object."""
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


def make_frame(cols, domains):
    """Host columns -> a device frame; columns with a domain are categorical
    codes. Returns when every column is on the device."""
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.vec import T_CAT
    have = {k: v for k, v in domains.items() if k in cols}
    frame = Frame.from_numpy(cols, types={k: T_CAT for k in have}, domains=have)
    sync_frame(frame)
    return frame


def sync_frame(frame):
    """Wait for a frame's device work (``bench_util.sync_frame``, which PR 24
    checked on the chip against a one-element fetch)."""
    jax.block_until_ready([v.data for v in frame.vecs if v.data is not None])


def data_state(seed, data):
    """The state every driver starts from: the configuration's data, which
    its generator made from the seed on the host (the references read it
    there), uploaded as a frame."""
    cols, domains, response = data
    features = [c for c in cols if c != response]
    state = {
        "seed": seed, "cols": cols, "domains": domains, "response": response,
        "features": features, "rows": len(cols[response]),
        "categorical": {f for f in features if f in domains},
        "make_frame": lambda some: make_frame(some, domains),
    }
    state["frame"] = make_frame(cols, domains)
    return state


def estimator(cfg, state, **override):
    """The configuration's estimator with its parameters."""
    params = {**cfg["params"], **override, "response_column": state["response"]}
    return resolve(cfg["estimator"])(**params)


def model_ready(model):
    """Wait for whatever a trained model still has in flight on the device."""
    for value in model.output.values():
        if not isinstance(value, jax.Array) and hasattr(value, "__dict__"):
            value = vars(value)     # a tree model's stacked ensemble: an object around arrays
        jax.block_until_ready(value)
