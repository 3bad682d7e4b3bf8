"""Device time of the chunk step's executions per chunk stepped."""
import trace_reduce


def read(run):
    return trace_reduce.step_us(run.trace)
