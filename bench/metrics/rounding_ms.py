"""Mean host rounding time per window solve, in ms, from
``SolveResult.timings["rounding"]`` (the program's span around
``core/rounding.py``)."""


def read(run):
    if not run.solves:
        return None
    return 1e3 * sum(s["rounding_s"] for s in run.solves) / len(run.solves)
