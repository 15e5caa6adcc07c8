"""Programs compiled or loaded from the compile cache after set-up.
Reads 0 when set-up warmed every shape the traffic uses."""


def read(run):
    return float(run.compiles)
