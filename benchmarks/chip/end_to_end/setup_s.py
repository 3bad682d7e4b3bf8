"""Process start to the first timed event: JAX and TPU start-up, stream
sampling, router construction, compilation or compile-cache reads, warm-up."""


def read(run):
    return run.setup_s
