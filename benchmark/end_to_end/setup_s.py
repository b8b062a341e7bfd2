"""Process start to the first instant of the window: imports, the backend,
the inputs, the world and its tables, warm-up and, in a run that compiles,
compilation."""


def read(run):
    return run.setup_s
