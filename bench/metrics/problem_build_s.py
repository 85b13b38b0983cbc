"""Seconds the Problem's build took in set-up (partition, reorder), by the
harness's clock: ``Problem.build`` on the session entry, the server's own
session build on the server entry, each try of the block-count search
included."""


def read(run):
    return run.setup.get("problem_build_s")
