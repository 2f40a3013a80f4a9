"""Wall time of the window divided by the requests it completed (ms).

The window runs from its start to the return of its last request, so it
holds every request's host side too: request inputs, transfers, the
compiled loop program and the gather of the colors to the host.
"""


def read(run):
    return run.window_s / len(run.requests) * 1e3
