"""Readings from the program's own spans. A traced run turns the program's
tracer on (``telemetry: 1``) and ``jobs/train.py`` copies what it recorded
into ``run.spans`` as (name, start on ``time.perf_counter()`` in seconds,
duration in seconds): the set-up spans of ``cli._build_trainer``
(``build-trainer``, ``load-data``, ``alias-table``), the loop's
(``prefetch-wait``, ``h2d``, ``step``, ``drain``, ``finalize``) and the
producer thread's (``produce``, ``queue-full``). A program that records no
span of a name gives ``None`` here, never an error."""


def total_s(run, name: str):
    """Seconds under every span of the name, in the whole run."""
    durs = [d for n, _, d in run.spans if n == name]
    return sum(durs) if durs else None


def window_share_pct(run, name: str):
    """The spans of the name that start inside the window, over the window
    (the rule ``train.input_wait_share`` has)."""
    durs = [d for n, s, d in run.spans if n == name and run.t0 <= s <= run.t1]
    if not durs or run.window_s <= 0:
        return None
    return 100.0 * sum(durs) / run.window_s


def steps_in(run, t0: float, t1: float) -> int:
    """The program's ``step`` spans (one per dispatched step) whose dispatch
    falls in [t0, t1] of the host's clock: those that end after t0 and start
    by t1. (The window opens inside the first one: the job's probe stands
    around the jitted step, within the loop's ``step`` span, so that span
    starts a moment before t0; the warm steps' spans end before it.)"""
    return sum(1 for n, s, d in run.spans if n == "step" and s + d > t0 and s <= t1)
