"""Share of its roofline that the fused residual kernel reaches.

Each call forms r = b - A x for the whole slot block: it must read A
(n x n float32), x and b and write r (n x max_batch each), and do
2 n^2 max_batch flops; against HBM bandwidth and the bf16 peak. The
time is the kernel's device time on device 0.
"""
from harness import counts

KERNEL = "residual_fused"


def read(run):
    if run.summary is None:
        return None
    sec, calls = run.summary.op_seconds(KERNEL)
    if not calls:
        return None
    n, k = run.config["n"], run.traffic["max_batch"]
    pk = run.peaks
    share, _ = counts.roofline_share(
        calls * counts.residual_flops(n, n, k),
        calls * counts.residual_bytes(n, n, k), sec,
        pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return share
