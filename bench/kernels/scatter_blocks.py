"""scatter_blocks: write the selected blocks of a stacked leaf in place.
Call: rows, cols = n_sel * block, itemsize. Least work: the values read
and the blocks written once; no FLOPs."""

NAMES = ("scatter_blocks",)


def cost(call: dict) -> tuple:
    return 0, 2 * call["rows"] * call["cols"] * call["itemsize"]
