#!/usr/bin/env python3
"""Compiles the four-chip round's two device programs for ``v5e:2x2``
without a chip, at the configuration's real size, and prints what the
compiler says of memory and collectives.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_rounds_4c.py [cell]

Run it before a four-chip call: what the TPU's compiler refuses here costs
no chip time. Nothing runs, so it says nothing of results or times. The
program builds its mesh from real devices and places real arrays, so this
script hands it the described devices and steers three things, here and
not through an option of the program: ``jax.default_backend()`` answers
``tpu``, and table creation gets shapes instead of arrays.
"""

from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.harness import cells
    cell = cells.load_cell(argv[0] if argv else "tables_rounds_4c")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"      # the program's kernel gate

    import multiverso_tpu as mv
    from multiverso_tpu.parallel import mesh as mesh_mod
    from multiverso_tpu.tables import MatrixTableOption, matrix_table
    from multiverso_tpu.updaters.base import AddOption

    def struct(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    class ShapesOnly:
        """``jnp`` for table creation: zeros are shapes."""
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def zeros(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

    matrix_table.jnp = ShapesOnly()
    mesh_mod.MeshContext.place = lambda self, a, sharding: struct(a, sharding)
    mv.MV_Init(list(cell.config.get("world_flags", [])),
               devices=topo.devices[: cell.chips])
    try:
        table = mv.MV_CreateTable(MatrixTableOption(
            num_rows=int(cell.config["rows"]),
            num_cols=int(cell.config["cols"])))
        srv = table.server()
        matrix_table.jnp = jnp
        state = srv._state
        everywhere = NamedSharding(srv._mesh, P())
        opt = jax.tree.map(lambda x: struct(x, everywhere),
                           AddOption().as_jnp())
        print(f"table {tuple(state['data'].shape)} {state['data'].dtype} on "
              f"{len(srv._mesh.devices.ravel())} described chips, "
              f"{srv.shard_rows} stored rows a shard")
        for k in sorted({int(cell.traffic["input_ids"]),
                         int(cell.traffic["output_ids"])}):
            ids = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=everywhere)
            rows = jax.ShapeDtypeStruct((k, srv.num_cols), srv.dtype,
                                        sharding=everywhere)
            for name, compiled in (
                    ("gather", srv._gather_rows.lower(
                        state["data"], state["aux"], ids).compile()),
                    ("update", srv._update_rows.lower(
                        state, ids, rows, opt).compile())):
                text = compiled.as_text()
                found = {op: text.count(f" {op}(") for op in (
                    "all-reduce", "all-gather", "collective-permute",
                    "reduce-scatter", "all-to-all")}
                print(f"{name} of {k} ids: compiled for v5e:2x2; "
                      f"tpu_custom_call x{text.count('tpu_custom_call')}, "
                      f"collectives {({o: n for o, n in found.items() if n})}")
                print(f"  {compiled.memory_analysis()}")
    finally:
        mv.MV_ShutDown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
