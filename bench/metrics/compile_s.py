"""``PlanStats.compile_ms`` of the cell's plan (compile or cache load), s."""


def read(run):
    return run.compile_s
