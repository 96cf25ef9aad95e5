"""Deadlines for work that cannot be interrupted from Python.

A hung Mosaic compile or kernel holds the main thread inside a native call:
no signal handler runs and no exception can be raised there. So the
deadline is enforced from a daemon thread, which says what was in flight
and leaves through ``os._exit``. Used by ``chip_smoke.py`` (one deadline
per leg plus one for the script) and ``tools/compile_probe.py`` (one per
kernel).
"""

import contextlib
import os
import threading
import time


class Watchdog:
    def __init__(self, tag: str, total_s=None, exit_code: int = 4):
        self._tag = tag
        self._exit_code = exit_code
        self._name = None
        self._what = ""
        self._until = None
        self._end = None if total_s is None else time.monotonic() + total_s
        self._closed = threading.Event()
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        while not self._closed.wait(0.5):
            now = time.monotonic()
            for limit in (self._until, self._end):
                if limit is not None and now > limit:
                    print(f"{self._tag}: WATCHDOG {self._name} passed its "
                          f"deadline; in flight: {self._what or 'unknown'}; "
                          "leaving", flush=True)
                    os._exit(self._exit_code)

    def close(self) -> None:
        """Stop watching (the thread ends; nothing can fire afterwards)."""
        self._closed.set()

    def note(self, what: str) -> None:
        """Say what is in flight now, for the message if the deadline hits."""
        self._what = what

    @contextlib.contextmanager
    def watch(self, name: str, deadline_s: float):
        """Run the body under ``deadline_s``."""
        self._name, self._what = name, ""
        self._until = time.monotonic() + deadline_s
        try:
            yield
        finally:
            self._until = None
