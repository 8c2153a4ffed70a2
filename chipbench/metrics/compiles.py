"""Backend compiles inside the measured window (JAX's monitoring
events, counted by the benchmark): 0 when set-up warmed every shape."""


def read(run):
    return float(run.compiles)
