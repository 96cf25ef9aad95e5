"""Backend compiles that ended inside the measured window; must read 0."""


def read(run):
    return float(run.compile_log.inside(run.t0, run.t1))
