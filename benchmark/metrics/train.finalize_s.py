"""Seconds ``TrainLoop.run`` spent after its last step on anything but
waiting for the device (that is ``drain``): the program's ``finalize`` spans
(teardown of the prefetcher and the capture, flushes, run record, joins)."""

from lib import spans


def read(run):
    return spans.total_s(run, "finalize")
