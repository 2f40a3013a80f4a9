"""Process start to window start (s): imports, device init, graph build
and partition, plan build, compile or cache load, and the warm-up."""


def read(run):
    return run.setup_s
