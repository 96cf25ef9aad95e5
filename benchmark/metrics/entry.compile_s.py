"""Backend compile seconds of the whole run, from jax's monitoring events."""


def read(run):
    return run.compile_log.seconds()
