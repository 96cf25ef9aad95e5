"""Share of the window the training loop spent handing a batch to the
device: the program's ``h2d`` spans over the window."""

from lib import spans


def read(run):
    return spans.window_share_pct(run, "h2d")
