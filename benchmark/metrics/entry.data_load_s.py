"""Seconds the trainer spent reading its data at set-up: the program's
``load-data`` span (Word2Vec: vocabulary scan and corpus encode; the CTR
models: the text parse), inside ``build-trainer``."""

from lib import spans


def read(run):
    return spans.total_s(run, "load-data")
