"""Refinement sweeps per column per solve, as the program reports them
(``RefineResult.iterations``), averaged over the window's solves."""


def read(run):
    return run.stats["counters"].get("refine.sweeps")
