"""Share of the window the training loop spent waiting for its next batch:
the program's ``prefetch-wait`` spans (``telemetry: 1``, traced runs only)."""


def read(run):
    waits = [d for name, s, d in run.spans if name == "prefetch-wait" and run.t0 <= s <= run.t1]
    if not waits:
        return None
    return 100.0 * sum(waits) / run.window_s
