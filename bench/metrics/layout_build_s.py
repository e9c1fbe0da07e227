"""Host-clock seconds of set-up spent building the program's device layout
from the graph (``PallasGraph.build``, ``DeviceGraph``; for serving, the
``PPREngine`` constructor)."""


def read(run, trace):
    return run.facts["layout_build_s"]
