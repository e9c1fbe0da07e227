"""The least time one sweep's required work takes on the chip (every rank
row of the batch, ``bench/work.py``), over the device's busy time per sweep
in the traced window.  Reads every ``sweep_roofline.<cell kind>`` metric."""
from bench.work import roofline_share as read  # noqa: F401
