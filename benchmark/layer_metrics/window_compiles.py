"""Programs compiled inside the window: JAX's compile requests there
minus the ones its persistent cache served; must be 0. (Requests that the
cache serves are printed on an earlier line of the run: the WordEmbedding
app re-jits its block program in every ``train()`` call and loads it from
the cache.) Layer: entry points. Moves the cell's throughput."""


def read(run):
    return run.compiles_window.get("compiled")
