"""Events whose assignments reached the sink within the window, per second
of the window."""


def read(run):
    arrived = run.sink_sizes[run.sink_times <= run.t0 + run.seconds].sum()
    return float(arrived) / run.seconds
