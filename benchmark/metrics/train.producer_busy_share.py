"""Share of the window the prefetcher's producer thread spent making batches:
its ``produce`` spans (each ``next()`` on the trainer's source) over the
window. What is left of 100 is the producer's room."""

from lib import spans


def read(run):
    return spans.window_share_pct(run, "produce")
