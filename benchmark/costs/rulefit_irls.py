"""What one IRLSM pass of RuleFit's lasso path needs at least, whatever
forms its products (``glm_irls.py`` on RuleFit's design in code form).

Bytes: the design read once, a float32 per numeric linear term and an int32
code per rule group (a tree at a rule depth), with the response, the row
weight and the offset.  Operations, in the sparse form: a row lights one
column per numeric, one level per rule group and the intercept, so its share
of X'WX is (numerics + groups + 1)^2 multiply-adds, 2 operations each; the
expanded width (400 rule columns here) does not enter.  One pass is one
step: the counters give the passes of a launch."""


def cost(state):
    params = state["cfg"]["params"]
    rows, numerics = state["rows"], len(state["features"])
    groups = params["rule_generation_ntrees"] * (
        params["max_rule_length"] - params["min_rule_length"] + 1)
    return {"bytes": rows * 4 * (numerics + groups + 3),
            "ops": 2 * rows * (numerics + groups + 1) ** 2, "steps": 1}
