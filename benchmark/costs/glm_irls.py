"""What one IRLSM pass over the frame needs at least, whatever forms its
products.

Bytes: the design in code form read once (a float32 per numeric, an int32
code per categorical) with the response, the row weight and the offset.
Operations, in the sparse form: a row lights one column per predictor and
the intercept, so its share of X'WX is (columns + 1)^2 multiply-adds, 2
operations each; the expanded width (628 here) does not enter, since an
implementation that multiplies no zeros does not pay for it. One pass is
one step: the counters give the passes of a launch.
"""


def cost(state):
    rows, columns = state["rows"], len(state["features"])
    return {"bytes": rows * 4 * (columns + 3),
            "ops": 2 * rows * (columns + 1) ** 2, "steps": 1}
