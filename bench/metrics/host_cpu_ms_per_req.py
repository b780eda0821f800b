"""User plus system CPU time of the server process and every client
process over the window, per request completed in it."""


def read(run):
    n = run.completed_in_window()
    return run.cpu_s * 1e3 / n if n else None
