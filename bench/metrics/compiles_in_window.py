"""Programs compiled inside the measured window (JAX's backend-compile
events between the window's start and end; warm-up should leave none).
Reads every ``compiles_in_window.<cell kind>`` metric."""


def read(run, trace):
    return run.facts["compiles_in_window"]
