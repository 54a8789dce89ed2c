"""What one ``jit_rule_codes`` launch of a RuleFit fit needs at least: the
design columns its trees split on read once (all of them: 50 trees of 7
splits each over 28 columns leave few unread) and one int32 code a row for
each tree and rule depth written once.  Operations: one compare a row, tree
and level (the walk's least work; the selects that fetch a node's split are
not counted).  One launch a fit."""


def cost(state):
    params = state["cfg"]["params"]
    rows, columns = state["rows"], len(state["features"])
    depth = params["max_rule_length"]
    groups = params["rule_generation_ntrees"] * (depth - params["min_rule_length"] + 1)
    return {"bytes": rows * 4 * (columns + groups),
            "ops": rows * params["rule_generation_ntrees"] * depth}
