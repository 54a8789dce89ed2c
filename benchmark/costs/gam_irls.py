"""What one IRLSM pass of GAM's penalized fit needs at least, whatever
forms its products (``glm_irls.py`` on a design whose smooths are built
from their raw columns).

Bytes: the design in code form read once, a float32 per column (a smooth's
raw values, or a numeric's), with the response, the row weight and the
offset.  Operations: the Gram of the expanded row, 2 (basis columns + 1)^2
a row, since every basis column is dense; and the basis itself, each
smooth's cardinal row (two values' weights and two second derivatives'
at a row's knot interval) through its K x (K - 1) tables: 2 K (K - 1)
multiply-adds, 2 operations each.  One pass is one step: the counters give
the passes of a launch."""


def cost(state):
    params = state["cfg"]["params"]
    smooths = len(params["gam_columns"])
    knots = params["num_knots"] if isinstance(params["num_knots"], list) \
        else [params["num_knots"]] * smooths
    rows, columns = state["rows"], len(state["features"])
    basis = sum(k - 1 for k in knots) + columns - smooths
    return {"bytes": rows * 4 * (columns + 3),
            "ops": 2 * rows * (basis + 1) ** 2
            + sum(2 * rows * 2 * k * (k - 1) for k in knots),
            "steps": 1}
