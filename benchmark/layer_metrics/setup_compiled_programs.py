"""Programs this process compiled during set-up: JAX's compile requests
minus the ones its persistent cache served. 0 in every run after a
checkout's first. Layer: entry points. Moves ``setup_s``."""


def read(run):
    return run.compiles_setup.get("compiled")
