"""Spread of compute time over the chips of a sharded cell: per whole
traced solve, each chip's seconds in operations other than collectives
(``harness.scopes_dist``); the busiest chip's less the idlest's, over
the busiest's, in percent. A chip that waits for another does so
inside a collective, so the waits are left out: 0 where every chip
computes alike; the 1-D block-row layout gives the last chip most of
the trailing update."""
from harness import scopes_dist


def read(run):
    if run.summary is None or len(run.devices) < 2:
        return None
    sec = scopes_dist.seconds_per_solve(run, scopes_dist.computing)
    if not sec or len(sec) < 2 or not max(sec.values()):
        return None
    return 100.0 * (max(sec.values()) - min(sec.values())) / max(sec.values())
