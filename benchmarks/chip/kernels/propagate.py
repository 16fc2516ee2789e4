"""Least work of one fused propagation call, from its shapes.

Counts what the algorithm needs, not what a kernel happens to do: the
top-k columns it reads (all k for numeric, the first for top1), the (C,)
rep scores, and the (N,) output, each once; and its arithmetic per
(record, column).  Padding, copies and the in-kernel one-hot gather are
the implementation's and are not counted.
"""

#: fragment of the XLA module name of the jitted call
MODULE = "_propagate_impl"


def work(records: int, k: int, reps: int, mode: str) -> dict:
    """{"flops", "bytes"} of one call over ``records`` rows."""
    if mode == "numeric":
        # per (row, column): sqrt, add eps, reciprocal, weight * score,
        # two running sums; per row one division
        return {"flops": 6 * records * k + records,
                "bytes": records * k * (4 + 4) + reps * 4 + records * 4}
    if mode == "top1":
        # per row: sqrt, scale, multiply, subtract; plus the max of d
        return {"flops": 5 * records,
                "bytes": records * (4 + 4) + reps * 4 + records * 4}
    raise ValueError(f"no work count for propagation mode {mode!r}")
