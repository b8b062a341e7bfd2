"""multiverso_tpu — a TPU-native parameter-server framework.

A ground-up re-design of the capabilities of Microsoft Multiverso
(C++11 MPI/ZMQ parameter server; see /root/reference) for TPU hardware:

* table shards live as JAX arrays in HBM, sharded over a ``jax.sharding.Mesh``
  "server" axis (replacing per-process C++ heap shards),
* server-side updaters (add / SGD / momentum / per-worker AdaGrad) run as
  jit'd XLA ops on the shards (replacing OpenMP loops,
  reference src/updater/updater.cpp:21-29),
* the Get/Add push-pull runs through sharded gather / scatter-add
  computations whose cross-chip movement is XLA ICI collectives
  (replacing MPI/ZMQ message transports, reference src/net*),
* ``MV_Aggregate`` model-average mode maps to ``psum`` over the mesh
  (replacing MPI_Allreduce and the Bruck/recursive-halving
  AllreduceEngine, reference src/net/allreduce_engine.cpp),
* the async / BSP(sync) / model-average consistency modes are preserved
  behaviorally, including the SyncServer vector-clock guarantee
  (reference src/server.cpp:60-67).

Public API mirrors the reference's ``MV_*`` surface
(reference include/multiverso/multiverso.h).

The ``MV_*`` surface is LAZY (PEP 562): importing the bare package does
not pull ``api`` → ``zoo`` → jax. That is what lets the replica plane's
jax-free reader processes (``multiverso_tpu/replica/replica.py``) import
their subpackage from this package without jax ever entering the import
graph — the first ``multiverso_tpu.MV_*`` attribute access triggers the
full training-plane import exactly as before.
"""

#: everything the eager ``from multiverso_tpu.api import ...`` used to
#: re-export — resolved on first attribute access
_API_NAMES = (
    "MV_Init",
    "MV_ShutDown",
    "MV_Barrier",
    "MV_Rank",
    "MV_Size",
    "MV_NumWorkers",
    "MV_NumServers",
    "MV_WorkerId",
    "MV_ServerId",
    "MV_WorkerIdToRank",
    "MV_ServerIdToRank",
    "MV_CreateTable",
    "MV_SetFlag",
    "MV_MultiAdd",
    "MV_MultiAddAsync",
    "MV_MultiGet",
    "MV_MultiGetAsync",
    "MV_Aggregate",
    "MV_NetBind",
    "MV_NetConnect",
    "MV_NetFinalize",
    "MV_SaveCheckpoint",
    "MV_LoadCheckpoint",
    "MV_PublishSnapshot",
    "MV_ServingLookup",
    "MV_PinVersion",
    "MV_UnpinVersion",
    "MV_StartProfiler",
    "MV_StopProfiler",
    "MV_MetricsSnapshot",
    "MV_DumpTrace",
    "MV_DumpFlightRecorder",
    "MV_DumpDiagnostics",
    "MV_ElasticSync",
    "MV_ElasticLeave",
    "MV_ElasticJoin",
    "MV_ElasticEpoch",
    "MV_ElasticMembers",
    "MV_PolicySync",
    "MV_PolicyReport",
    "MV_PolicyKill",
    "MV_WorkerContext",
)

__version__ = "0.1.0"

__all__ = list(_API_NAMES) + ["__version__"]


def __getattr__(name: str):
    if name in _API_NAMES:
        # the training plane's import (api -> zoo -> jax) is the first
        # phase of a start: gauge mv.import_s (telemetry/startup.py)
        from multiverso_tpu.telemetry import startup
        with startup.phase("mv.import"):
            from multiverso_tpu import api
        value = getattr(api, name)
        globals()[name] = value     # cache: one import per process
        return value
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_API_NAMES))
