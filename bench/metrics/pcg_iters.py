"""Mean PCG steps per window solve: the host loop's
``diagnostics.pcg_iters``, or the scanned program's
``SolveResult.pcg_iters``, summed over the IRLS iterations."""


def read(run):
    if not run.solves:
        return None
    return sum(s["pcg_iters"] for s in run.solves) / len(run.solves)
