"""What one inner join of the two tables needs at least, in bytes moved
through HBM: both tables read once (every column, 4 bytes a value), the
output written once (its columns x its rows), and each sort's operands read
and written once. A sort-merge on this chip makes four sorts (device.py):
keys and row numbers of both tables together (2 operands x (left + right)
rows), the match counts and starts back to left-row order (3 operands x the
same), the merge of running totals and output slots (1 operand x (left +
output) rows) and the slots' compaction (3 operands x the same). The
output's row count is not known from shapes: the driver records what the
last join gave (``out_rows``; the seeds of the gate's tables give 91 % of
the left table's rows). Rows are real rows: the padding the programs add
(the output in steps of 1/64 of the left table) is theirs to pay, not part
of the least. No operation count:
a join does no arithmetic to speak of, and peaks.json has no sort rate, so
the bound is by bytes and the share says how far the programs are from
streaming their operands once.
"""


def cost(state):
    left, right = state["rows"], state["right_rows"]
    out = state["out_rows"]
    tables = 4 * (2 * left + 2 * right)
    output = 4 * 3 * out
    sorts = 2 * 4 * ((2 + 3) * (left + right) + (1 + 3) * (left + out))
    return {"bytes": tables + output + sorts, "ops": 0}
