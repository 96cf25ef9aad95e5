"""Seconds in ``cli._build_trainer``: the program's ``build-trainer`` span
(the tracer is made there, where the config is first in hand)."""

from lib import spans


def read(run):
    return spans.total_s(run, "build-trainer")
