"""What scoring one frame through a stacked tree ensemble needs at least.

Bytes: every feature value read once (float32) and one float32 margin
written per row, plus the trees' own tables (feature, threshold, NA
direction, validity per inner node; one value per leaf). Operations: per row,
tree and level one table look-up, one compare and one index update.
"""


def cost(state):
    rows, features = state["rows"], len(state["features"])
    trees, depth = state["ntrees"], state["depth"]
    tables = trees * ((2 ** depth - 1) * 4 + 2 ** depth) * 4
    return {"bytes": rows * features * 4 + rows * 4 + tables,
            "ops": 3 * rows * trees * depth}
