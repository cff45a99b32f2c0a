"""Mean wait from submit to the start of a request's solve, from the
program's own ``scheduler.queue_ms`` series over the window."""


def read(run):
    return run.stats["counters"].get("sched.queue_mean_ms")
