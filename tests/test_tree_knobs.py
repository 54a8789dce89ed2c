"""The values the four tree kernel knobs accept, at both doors.

``"check"`` (train both ways inside the fit) left ``hist_mode``,
``split_mode``, ``hist_layout`` and ``tree_program``, and ``split_search``
left the parameters with the hierarchical search it selected.  Both are
refused when the estimator is built, before a frame or a device is touched,
in Python and over the REST model-builder route, and the message says what
is accepted.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.api.server import start_server
from h2o3_tpu.models import GBM

REFUSED = [
    ("hist_mode", "check", ("auto", "subtract", "full")),
    ("split_mode", "check", ("auto", "fused", "separate")),
    ("hist_layout", "check", ("auto", "dense", "sparse")),
    ("tree_program", "check", ("auto", "level", "scan")),
    # no value of a parameter that is gone is accepted: the message names it
    ("split_search", "hier", ("split_search",)),
]
IDS = [f"{knob}={value}" for knob, value, _ in REFUSED]


@pytest.fixture(scope="module")
def server(cl):
    r = np.random.default_rng(0)
    x = r.normal(size=200)
    Frame.from_numpy({"x": x, "y": x + 0.1 * r.normal(size=200)},
                     key="tree_knobs_fr")
    srv = start_server(port=0)
    yield srv
    srv.stop()


@pytest.mark.parametrize("knob,value,accepted", REFUSED, ids=IDS)
def test_estimator_refuses(cl, knob, value, accepted):
    with pytest.raises((ValueError, TypeError)) as e:
        GBM(response_column="y", **{knob: value})
    assert knob in str(e.value)
    assert all(word in str(e.value) for word in accepted), str(e.value)


@pytest.mark.parametrize("knob,value,accepted", REFUSED, ids=IDS)
def test_rest_model_builder_refuses(server, knob, value, accepted):
    req = urllib.request.Request(
        f"{server.url}/3/ModelBuilders/gbm", method="POST",
        data=json.dumps({"training_frame": "tree_knobs_fr",
                         "response_column": "y", "ntrees": 1,
                         knob: value}).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=60)
    assert e.value.code == 400
    error = json.loads(e.value.read())["error"]
    assert knob in error
    assert all(word in error for word in accepted), error
