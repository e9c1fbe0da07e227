"""The share of the traced window in which no operation ran on the chip.
Reads every ``device_idle.<cell kind>`` metric."""


def read(run, trace):
    return 100.0 * trace.idle_share
