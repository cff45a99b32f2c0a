"""Device milliseconds per solve in collectives (all-gather,
all-reduce, collective-permute, reduce-scatter, all-to-all, with their
async start and done halves, found by HLO opcode), on the chip that
spent the most in them, over the solves the trace holds whole
(``harness.scopes_dist``). A chip that waits for another inside a
collective counts the wait here."""
from harness import scopes_dist


def read(run):
    if run.summary is None or len(run.devices) < 2:
        return None
    sec = scopes_dist.seconds_per_solve(run, scopes_dist.collective)
    if not sec or not any(sec.values()):
        return None
    return max(sec.values()) * 1e3
