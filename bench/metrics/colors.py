"""The most colors any checked answer of the window returned (count).

Counted on the answers the reference checks (``bench.run.CHECKED`` of
them, drawn from the seed); the window keeps no other.
"""


def read(run):
    return max(r["colors"] for r in run.requests if "colors" in r)
