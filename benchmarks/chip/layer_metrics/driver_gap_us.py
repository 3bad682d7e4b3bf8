"""Device-idle time per chunk stepped while the host is inside route_stream
but neither in the generator nor in the sink: the driver's own host work
(rebuffering, device_put, dispatch, the blocking pull) left uncovered."""


def read(run):
    t = run.trace
    if t is None or t.steps == 0:
        return None
    return t.idle_ns["driver"] / t.steps * 1e-3
