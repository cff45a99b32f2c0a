"""Device milliseconds per solve in the factorization's kernels: the
fused panel update, the leaf POTRF and the leaf triangular inverse."""
KERNELS = ("panel_update", "potrf_leaf", "tri_inv_leaf")


def read(run):
    if run.summary is None:
        return None
    sec, calls = run.summary.op_seconds(KERNELS)
    if not calls:
        return None
    return sec / run.stats["solves"] * 1e3
