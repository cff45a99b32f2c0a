"""Share of its roofline that the distributed trailing update reaches.

The count is a solve's useful update work, a chip's share of it
(``harness.counts_dist``: n^3 / 3 flops and the lower triangle read and
written, over the number of chips), against the chip's bf16 peak and
HBM bandwidth; the time is the device time per whole traced solve under
the program's ``factor/dist/update`` scope on the chip that spent the
most there (``harness.scopes_dist``).
"""
from harness import counts, counts_dist, scopes_dist

SCOPE = ("factor", "dist", "update")


def read(run):
    if run.summary is None:
        return None
    sec = scopes_dist.seconds_per_solve(run, scopes_dist.under(SCOPE))
    if not sec or not max(sec.values()):
        return None
    n, chips = run.config["n"], len(run.devices)
    pk = run.peaks
    share, _ = counts.roofline_share(
        counts_dist.update_flops(n) / chips,
        counts_dist.update_bytes(n) / chips, max(sec.values()),
        pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return share
