"""The most colors any request of the window returned (count)."""


def read(run):
    return max(r["colors"] for r in run.requests)
