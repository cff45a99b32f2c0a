"""Share of the traced window in which device 0 ran no operation."""


def read(run):
    if run.summary is None:
        return None
    return run.summary.idle_pct(0)
