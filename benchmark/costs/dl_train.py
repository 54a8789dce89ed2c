"""What one DeepLearning fit's optimizer steps need at least, whatever
implements the first layer.

Steps: as ``DeepLearning._fit`` sizes them at its shipped defaults
(``train_samples_per_iteration=-2``): iterations of max(rows // 10, 16
minibatches) samples, as many as fit into ``epochs`` x rows.

Bytes per step: every parameter and both ADADELTA accumulators (E[g^2],
E[D^2]) read and written once in float32, and the minibatch read once (a
float32 per numeric, an int32 code per categorical, label and weight). The
parameters are those of the expanded first layer: one input per numeric, per
categorical one per level but the first and one for NA, the intercept. The
bytes are priced at the HBM rate of ``peaks.json``: that is the least time
of a step that streams its state from HBM, NOT of one that keeps the state
in VMEM across steps, as XLA does today (PERF.md section 7); ``peaks.json``
has no VMEM rate to price that with.

Operations per sample, in the sparse form: a row lights one input per column
of the frame, so the first layer is columns x hidden[0] multiply-adds; 2
operations each, three times over for forward, weight gradient and input
gradient.
"""

MINI_BATCH_SIZE = 128       # DeepLearningParameters.mini_batch_size, shipped


def geometry(state):
    """(iterations, optimizer steps in each, minibatch rows, layer sizes
    with the expanded input width first, columns of the frame that are
    features). An iteration is one launch of the training program."""
    params, rows = state["cfg"]["params"], state["rows"]
    batch = min(params.get("mini_batch_size", MINI_BATCH_SIZE), rows)
    steps_per_iter = max(max(rows // 10, batch * 16) // batch, 1)
    iters = max(int(params["epochs"] * rows) // (steps_per_iter * batch), 1)
    expanded = 1 + sum(len(state["domains"][f]) if f in state["categorical"] else 1
                       for f in state["features"])
    sizes = [expanded, *params["hidden"], len(state["domains"][state["response"]])]
    return iters, steps_per_iter, batch, sizes, len(state["features"])


def flops(state):
    iters, steps, batch, sizes, columns = geometry(state)
    macs = columns * sizes[1] + sum(a * b for a, b in zip(sizes[1:-1], sizes[2:]))
    return 6 * macs * batch * steps * iters


def cost(state):
    iters, steps, batch, sizes, columns = geometry(state)
    parameters = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    minibatch = batch * 4 * (columns + 2)
    return {"bytes": iters * steps * (3 * 2 * 4 * parameters + minibatch),
            "ops": flops(state), "steps": iters * steps}
