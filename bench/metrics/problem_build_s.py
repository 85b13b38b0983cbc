"""Seconds ``Problem.build`` took in set-up (partition, reorder), by the
harness's clock around the call; session entries only."""


def read(run):
    return run.setup.get("problem_build_s")
