"""Share of the traced window in which no operation ran on the device:
1 − (union of the device's operation intervals) / window, in percent."""
from metrics._common import idle


def read(run):
    return idle(run)
