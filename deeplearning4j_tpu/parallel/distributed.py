"""Multi-host distributed backend.

TPU-native replacement for the reference's multi-node stacks (SURVEY §2.5
strategies 3-4): Spark parameter averaging (ParameterAveragingTrainingMaster)
and the Aeron UDP VoidParameterServer (SharedTrainingMaster/
SharedTrainingWrapper.java:206-244, SilentTrainingDriver threshold-compressed
async updates).

On TPU both collapse to the same synchronous SPMD program: `jax.distributed`
brings up the gRPC coordination service over DCN; every host runs the SAME
jitted train step over a global mesh whose "data" axis spans all chips in the
job; XLA routes gradient allreduce over ICI within a slice and DCN across
slices. Gradient compression (EncodingHandler thresholdEncode) is dropped by
design — dense bf16/fp32 allreduce over ICI is faster than the reference's
sparse codec over UDP (BASELINE.json north star).

Spark's remaining role — data sharding — maps to per-host input pipelines:
each host feeds only its local shard of the global batch
(`host_local_batch` / `host_shard_bounds`), like Spark executors reading
their RDD partitions.

**Elastic lifecycle** (resilience/elastic.py + parallel/elastic.py): the
coordination service reacts to a lost peer by *terminating every other
task* — the exact cascade an elastic trainer must survive. Three
primitives here make the runtime survivable:

- ``elastic_initialize``: bring up jax.distributed with jax's own
  failure detector stood down (heartbeat timeout pushed out to hours via
  the internal ``State.initialize`` knob the public wrapper hides) so
  the lease ledger — not the gRPC service — owns failure detection.
- ``abandon_distributed``: detach from a DEAD generation without ever
  calling ``client.shutdown()`` (it blocks on a shutdown barrier the
  dead peer will never reach, and a clean shutdown attempt can itself
  trigger the terminate-everyone error path). The old client/service are
  parked on a module-level zombie list so their destructors never run;
  the distributed State fields are reset to single-process.
- ``reset_backend``: drop every live backend + compiled trace and flip
  the CPU collectives implementation (gloo needs a distributed client;
  a world-of-one must build without one) so the next jax call builds a
  fresh client against the CURRENT distributed state.
"""

from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

log = logging.getLogger(__name__)

_ENV_COORD = "JAX_COORDINATOR_ADDRESS"
_ENV_NPROC = "JAX_NUM_PROCESSES"
_ENV_PID = "JAX_PROCESS_ID"


@dataclass
class VoidConfiguration:
    """Connection info for the coordination service — name kept for API
    parity with the reference's VoidConfiguration (SharedTrainingMaster.java:58),
    but it configures jax.distributed (gRPC over DCN), not Aeron UDP."""

    coordinator_address: Optional[str] = None  # "host:port" of process 0
    num_processes: int = 1
    process_id: int = 0
    local_device_ids: Optional[Sequence[int]] = None

    @classmethod
    def from_env(cls) -> "VoidConfiguration":
        """Explicit parse of the standard jax.distributed env vars
        (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID).

        All three unset → a single-process configuration. Anything else
        must be COMPLETE and VALID: a partial or malformed set raises
        ``ValueError`` naming exactly what is wrong, instead of the old
        silent single-process fallback that turned a typo'd coordinator
        address into a 1/N-throughput job that "worked"."""
        raw = {k: os.environ.get(k)
               for k in (_ENV_COORD, _ENV_NPROC, _ENV_PID)}
        present = {k: v for k, v in raw.items() if v not in (None, "")}
        if not present:
            return cls()
        missing = [k for k, v in raw.items() if v in (None, "")]
        if missing:
            raise ValueError(
                f"partial jax.distributed environment: "
                f"{sorted(present)} set but {sorted(missing)} unset — "
                f"set all three of {_ENV_COORD}/{_ENV_NPROC}/{_ENV_PID} "
                f"or none")
        coord = raw[_ENV_COORD]
        host, sep, port = coord.rpartition(":")
        if not sep or not host or not port.isdigit():
            raise ValueError(
                f"{_ENV_COORD}={coord!r} is not host:port")
        try:
            nproc = int(raw[_ENV_NPROC])
        except ValueError:
            raise ValueError(
                f"{_ENV_NPROC}={raw[_ENV_NPROC]!r} is not an integer"
            ) from None
        try:
            pid = int(raw[_ENV_PID])
        except ValueError:
            raise ValueError(
                f"{_ENV_PID}={raw[_ENV_PID]!r} is not an integer"
            ) from None
        if nproc < 1:
            raise ValueError(f"{_ENV_NPROC}={nproc} must be >= 1")
        if not 0 <= pid < nproc:
            raise ValueError(
                f"{_ENV_PID}={pid} out of range for "
                f"{_ENV_NPROC}={nproc} (need 0 <= id < processes)")
        return cls(coordinator_address=coord, num_processes=nproc,
                   process_id=pid)


_initialized = False


def initialize(config: Optional[VoidConfiguration] = None) -> None:
    """Bring up the multi-host runtime (ref equivalent: VoidParameterServer
    .init at SharedTrainingWrapper.java:206-214 / Spark context setup).

    With config=None, settings come from the standard env vars (parsed
    and VALIDATED by ``VoidConfiguration.from_env`` — a partial or
    malformed set raises instead of silently running single-process) or
    the cloud TPU metadata that jax.distributed auto-detects.
    """
    global _initialized
    if _initialized:
        return
    if config is None or config.coordinator_address is None:
        if config is None:
            config = VoidConfiguration.from_env()  # raises on bad env
        if config.coordinator_address is None and _on_cloud_tpu():
            try:
                jax.distributed.initialize()
                _initialized = True
            except (ValueError, RuntimeError) as e:
                # TPU env vars present but no coordinator jax can
                # resolve from them: a one-host TPU VM. Say so loudly —
                # a misconfigured pod slice lands here too.
                log.warning("multi-host auto-init unavailable (%s); "
                            "single-process mode", e)
            return
        if config.coordinator_address is None:
            log.info("single-process mode (no coordinator configured)")
            return
    jax.distributed.initialize(
        coordinator_address=config.coordinator_address,
        num_processes=config.num_processes,
        process_id=config.process_id,
        local_device_ids=config.local_device_ids,
    )
    _initialized = True


def _on_cloud_tpu() -> bool:
    return bool(os.environ.get("TPU_WORKER_HOSTNAMES") or
                os.environ.get("TPU_NAME"))


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def is_initialized() -> bool:
    return _initialized


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def global_mesh(axis_names: Sequence[str] = ("data",),
                shape: Optional[Sequence[int]] = None):
    """Mesh over ALL devices in the job (every host's chips). With the
    default shape, the "data" axis spans the whole pod — the multi-host
    analogue of SparkDl4jMultiLayer's cluster-wide data parallelism."""
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh(shape=shape, axis_names=axis_names, devices=jax.devices())


def host_local_batch(global_batch_size: int,
                     rank: Optional[int] = None,
                     world: Optional[int] = None,
                     strict: bool = False) -> int:
    """Per-host share of a global batch (Spark-executor-partition
    analogue).

    Elastic world sizes rarely divide the global batch evenly (a 1024
    batch over a 3-survivor generation), so the default split is the
    LARGEST EVEN SPLIT with the remainder assigned one extra example to
    the lowest ranks: ``base = g // world`` everywhere, ranks
    ``0..(g % world)-1`` take ``base + 1``. Every example is consumed,
    shards differ by at most one, and the assignment is a pure function
    of (g, rank, world) — deterministic across re-meshes, which is what
    lets a survivor recompute its shard from the generation record
    alone. ``strict=True`` restores the pre-elastic contract: raise on
    any non-divisible batch (jobs that size batches to the pod and want
    loud failure when that invariant breaks).

    ``rank``/``world`` default to the live runtime (call-time reads —
    module-scope snapshots of either go stale after a re-mesh; tpulint
    rule ``stale-world-snapshot``)."""
    n = jax.process_count() if world is None else int(world)
    r = jax.process_index() if rank is None else int(rank)
    if not 0 <= r < n:
        raise ValueError(f"rank {r} out of range for world {n}")
    g = int(global_batch_size)
    rem = g % n
    if rem and strict:
        raise ValueError(f"global batch {g} not divisible by "
                         f"{n} processes")
    return g // n + (1 if r < rem else 0)


def host_shard_bounds(global_batch_size: int,
                      rank: Optional[int] = None,
                      world: Optional[int] = None,
                      strict: bool = False) -> Tuple[int, int]:
    """Contiguous ``[lo, hi)`` row range of this host's shard under the
    ``host_local_batch`` split: lo = sum of the shard sizes below this
    rank. Shards tile the global batch exactly (no gaps, no overlap) for
    every (batch, world) combination."""
    n = jax.process_count() if world is None else int(world)
    r = jax.process_index() if rank is None else int(rank)
    sizes = [host_local_batch(global_batch_size, rank=i, world=n,
                              strict=strict) for i in range(r + 1)]
    hi = sum(sizes)
    return hi - sizes[-1], hi


def make_global_array(local_batch: np.ndarray, mesh, spec=None):
    """Assemble a globally-sharded array from per-host local shards
    (jax.make_array_from_process_local_data) — the DCN-era equivalent of
    Spark broadcasting/partitioning DataSets to executors."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, spec if spec is not None
                             else P("data", *([None] * (local_batch.ndim - 1))))
    return jax.make_array_from_process_local_data(sharding, local_batch)


# ---------------------------------------------------------------------------
# elastic runtime lifecycle (resilience/elastic.py's jax-facing half)
# ---------------------------------------------------------------------------
#: abandoned coordination clients/services from dead generations. Their
#: destructors are never safe to run (a DistributedRuntimeClient
#: destructor attempts the shutdown barrier a dead peer will never
#: reach), so they are parked here for the life of the process. Elastic
#: worker processes should exit via os._exit so interpreter teardown
#: never walks this list.
_zombie_runtimes: List[object] = []


def elastic_initialize(coordinator_address: str, num_processes: int,
                       process_id: int,
                       initialization_timeout: float = 60.0,
                       heartbeat_timeout_seconds: int = 10_000) -> None:
    """``jax.distributed.initialize`` with jax's own failure detector
    stood down.

    The default coordination-service reaction to a missed heartbeat is
    to TERMINATE every remaining task (client.h: "Terminating process
    because the JAX distributed service detected fatal errors") — the
    opposite of elastic. The public ``jax.distributed.initialize``
    doesn't expose the heartbeat knob, so this goes through the
    internal ``State.initialize`` (jax 0.9.0 signature) and pushes the
    detection horizon out to ``heartbeat_timeout_seconds`` (default
    ~2.7 hours): the lease ledger detects a lost host in seconds and
    tears the runtime down long before jax's own detector ever fires."""
    global _initialized
    from jax._src import distributed as _jdist
    if _cpu_platform():
        # the CPU backend's cross-process collectives implementation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _jdist.global_state.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes), process_id=int(process_id),
        local_device_ids=None,
        cluster_detection_method="deactivate",
        initialization_timeout=int(initialization_timeout),
        heartbeat_timeout_seconds=int(heartbeat_timeout_seconds))
    _initialized = True


def _cpu_platform() -> bool:
    return jax.config.jax_platforms == "cpu"


def abandon_distributed() -> None:
    """Detach from a DEAD generation's coordination runtime without
    shutting it down.

    ``client.shutdown()`` blocks on the shutdown barrier until every
    registered task arrives — a SIGKILLed peer never will — and error
    propagation during the wait can terminate this process. Instead the
    live client/service objects are parked on the zombie list (keeping
    them referenced so no destructor ever runs) and the distributed
    State is reset to single-process, so the next backend build sees a
    clean world. Pair with ``reset_backend``."""
    global _initialized
    from jax._src import distributed as _jdist
    state = _jdist.global_state
    if state.client is not None:
        _zombie_runtimes.append(state.client)
    if state.service is not None:
        _zombie_runtimes.append(state.service)
    state.client = None
    state.service = None
    state.preemption_sync_manager = None
    state.process_id = 0
    state.num_processes = 1
    state.coordinator_address = None
    _initialized = False


def reset_backend(collectives: Optional[str] = None) -> None:
    """Drop every live backend, compiled trace, and device array binding
    so the next jax call rebuilds against the CURRENT distributed state.

    ``collectives`` sets ``jax_cpu_collectives_implementation`` first
    ("gloo" before re-joining a multi-process world, "none" before
    running world-of-one: the gloo CPU client refuses to build without a
    distributed client). Every jax.Array created before the reset is
    dead after it — restore state from host copies (the committed
    checkpoint) before touching the mesh again."""
    if collectives is not None and _cpu_platform():
        jax.config.update("jax_cpu_collectives_implementation",
                          collectives)
    import jax.extend.backend as _xb
    _xb.clear_backends()
    jax.clear_caches()


_teardown_lock = threading.Lock()


def teardown_dead_generation() -> None:
    """The survivor-side teardown: abandon the dead generation's
    coordination runtime and reset to a single-process CPU/TPU world.
    Idempotent; safe to call with a peer hung mid-collective (nothing
    here blocks on remote state)."""
    with _teardown_lock:
        abandon_distributed()
        reset_backend(collectives="none")
