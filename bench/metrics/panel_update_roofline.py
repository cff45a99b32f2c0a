"""Share of its roofline that the fused panel-update kernel reaches.

The count is the trailing update's useful work over the traced solves
(``harness.counts.panel_flops`` / ``panel_bytes``: the TRSM and the
lower half of the SYRK of every panel), against the chip's bf16 peak
and HBM bandwidth; the time is the kernel's device time on device 0.
"""
from harness import counts

KERNEL = "panel_update"


def read(run):
    if run.summary is None:
        return None
    sec, calls = run.summary.op_seconds(KERNEL)
    if not calls:
        return None
    n, leaf = run.config["n"], run.config["leaf"]
    solves = run.stats["solves"]
    pk = run.peaks
    share, _ = counts.roofline_share(
        solves * counts.panel_flops(n, leaf),
        solves * counts.panel_bytes(n, leaf), sec,
        pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return share
