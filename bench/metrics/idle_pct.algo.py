"""Share of the traced window in which no operation ran on the device:
``1 - busy / window`` from the profiler trace."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    idle = tr.idle_share()
    return None if idle is None else idle * 100.0
