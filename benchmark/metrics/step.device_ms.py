"""Device milliseconds per dispatched step: the operation time of the device
trace over the run's window, over the program's ``step`` spans in it
(``lib/scopes.py``). The model step's reading from inside."""

from lib import scopes


def read(run):
    return scopes.device_ms(run)
