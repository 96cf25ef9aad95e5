"""What the driver loops share. A loop is a file of its own,
``benchmark/jobs/<job>.py``, found by the traffic mix's ``job`` (today one:
``train``): it builds the system under test through the entry points a user
calls, hands it inputs and weights made from the seed, warms up, measures a
window on the host's clock, and then - the window closed, the peak read, the
program's state freed - has the reference follow what the timed path did. A
new kind of job is a new file there, with ``run(run, work_dir, t_process)``.

What belongs to one model (its feed, its weights in the program's layout,
what is read from its state, its reference, its operations and bytes) is in
``benchmark/models/<model>.py``, found by the configuration's ``model``: a
new model is a new file there.

A job fills a ``Run``: what the window counted, what was compared, and what
the per-layer readers (``benchmark/metrics/*.py``) read from.
"""

import dataclasses
import importlib.util
import os
import time

from . import trace as trace_lib

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Run:
    config: dict
    mix: dict
    seed: int
    seconds: float
    traced: bool
    chips: int = 1
    limits: dict = dataclasses.field(default_factory=dict)
    setup_s: float = 0.0
    t0: float = 0.0  # window, on time.perf_counter()
    t1: float = 0.0
    attempted: int = 0
    failed: int = 0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    numbers: dict = dataclasses.field(default_factory=dict)  # compared with limits
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)  # (name, start perf s, dur s)
    trace: dict = None  # reduce_trace's result, traced runs only
    memory_peak_bytes: int = 0
    device: dict = dataclasses.field(default_factory=dict)
    compile_log: object = None
    extra: dict = dataclasses.field(default_factory=dict)  # for control.py

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def model(self):
        """The module ``benchmark/models/<model>.py`` of the configuration."""
        return load_model(self.config["model"])


_LOADED = {}


def _load(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``, loaded once."""
    if (kind, name) not in _LOADED:
        path = os.path.join(HERE, kind, name + ".py")
        if not os.path.isfile(path):
            raise SystemExit(f"no {path}: a new one is a new file there")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[kind, name] = mod
    return _LOADED[kind, name]


def load_model(name: str):
    return _load("models", name)


def load_job(name: str):
    """The driver loop of a traffic mix's ``job``: its module has
    ``run(run, work_dir, t_process)``."""
    return _load("jobs", name)


# ---------------------------------------------------------------- tracing ---


class Profile:
    """The profiler around a traced run's window, which ``run.py`` cuts to
    the mix's ``trace_seconds``."""

    def __init__(self, work_dir: str):
        self.dir = os.path.join(work_dir, "trace")
        self.clock = None  # perf_counter_ns when the marker annotation was made
        self.neutral = self.window = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.clock = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("bench:clock"):
            pass

    def stop(self, run: Run, extra_spans=()):
        """Stop, read and reduce; ``extra_spans`` are (name, start perf s,
        dur s) host spans of the program's tracer, moved onto the trace's
        clock by the marker."""
        import jax

        jax.profiler.stop_trace()
        neutral = trace_lib.load_xplane(
            trace_lib.find_xplane(self.dir),
            host_keep=r"^(bench:|PjitFunction|PjRt|TfrtCpu|.*[Tt]ransfer|.*Execute|\$)")
        marks = [s for name, s, _ in neutral["host"] if name == "bench:clock"]
        if marks:
            shift = marks[0] - self.clock
            for name, start_s, dur_s in extra_spans:
                neutral["host"].append(
                    [name, int(start_s * 1e9) + shift, int(dur_s * 1e9)])
            w0 = int(run.t0 * 1e9) + shift
            w1 = int(min(run.t1, run.t0 + run.seconds) * 1e9) + shift
            window = (w0, w1)
        else:
            window = None
        neutral["host"] = [h for h in neutral["host"] if h[0] != "bench:clock"]
        self.neutral, self.window = neutral, window
        if run.device.get("platform") != "tpu":
            return  # the rehearsal has no device plane to reduce
        run.trace = trace_lib.reduce_trace(neutral, window)


def device_info():
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def memory_peak():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))
