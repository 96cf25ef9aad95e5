"""A driver loop that exists only in the tests' copy of the benchmark: the
``train`` job under another name, so that a mix which names it shows that a
job is added as a file, with no edit to another."""

from lib import jobs

_train = jobs.load_job("train")
program_config = _train.program_config


def run(run, work_dir, t_process, **kw):
    return _train.run(run, work_dir, t_process, **kw)
