"""Least work of one distance top-k call, from its shapes.

The algorithm reads the (N, D) records and (C, D) reps once and writes the
(N, k) distances and ids; it needs the N*C dot products (2*D flops each)
plus adding the two squared norms, and one comparison per (record, rep) to
keep the k smallest.
"""

#: fragment of the XLA module name of the jitted call
MODULE = "distance_topk"


def work(records: int, width: int, reps: int, k: int) -> dict:
    """{"flops", "bytes"} of one call."""
    return {"flops": 2 * records * reps * width + 3 * records * reps,
            "bytes": (records + reps) * width * 4 + records * k * (4 + 4)}
