"""Device busy time per IRLS iteration, in ms: the busy time of the traced
window (union of the chips' op intervals, mean over chips) over the IRLS
iterations of the solves in it.  Each iteration reweights the system,
factors the block-Jacobi preconditioner and runs its PCG steps; with
``pcg_iters.solve`` it splits ``solve_s`` into iterations and their
cost."""


def read(run):
    if run.trace is None or not run.solves:
        return None
    iters = sum(s["irls_iters"] for s in run.solves)
    return 1e3 * run.trace["busy_s"] / iters if iters else None
