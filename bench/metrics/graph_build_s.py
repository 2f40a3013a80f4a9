"""Host time of the program's CSR build and partition (s)."""


def read(run):
    return run.graph_build_s
