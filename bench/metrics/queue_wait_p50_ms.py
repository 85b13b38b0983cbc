"""Median wait of a window request in the server's queue, in ms: submit to
the start of its batch's execution, ``ServeMetrics.latency_ms("queue",
50)`` over the window (server entry only)."""


def read(run):
    if run.serve is None or not run.serve.completed:
        return None
    return run.serve.latency_ms("queue", 50)
