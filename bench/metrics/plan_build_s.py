"""``PlanStats.build_ms`` of the cell's plan, in s."""


def read(run):
    return run.plan_build_s
