"""Multi-host pod runtime: jax.distributed glue + all-to-all ingest exchange.

The reference scales by running one full pipeline per JVM host — there is no
cross-host fabric at all beyond the shared Postgres (SURVEY.md §5.8). The pod
model is stronger: service rows are partitioned across every chip in the pod,
and a transaction can be ingested by ANY host (wherever its log is tailed).
That requires a host-batch scatter to the owning shard, which here is the
device fabric itself — `lax.all_to_all` over the service-axis mesh — rather
than a host-side message broker:

1. each ingesting host routes its micro-batch into per-destination-shard
   blocks with :func:`route_batch` (vectorized, ~2.6M records/s),
2. the blocks become one global ``[n_shards(src), n_shards(dst), B]`` array —
   dim 0 sharded over the mesh, each device holding the blocks its host
   produced (`make_array_from_process_local_data` on multi-host, a plain
   sharded device_put single-host),
3. inside the jitted step, ``all_to_all`` transposes src->dst over ICI/DCN so
   every shard receives exactly the records it owns, which it scatter-ingests
   locally.

Single-chip, the exchange degenerates to an identity; on the 8-device CPU
test mesh it exercises the real collective. ``jax.distributed.initialize``
wiring lives in :func:`init_distributed` (env-var driven, no-op when
single-process) so the same module scripts run on a laptop, a v5e-8, or a
multi-host pod.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..pipeline import EngineConfig, EngineState, engine_ingest
from .mesh import SERVICE_AXIS
from .sharded import _state_specs, local_config, route_batch


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize the multi-host backend; returns True when distributed.

    Arguments default to the standard env vars (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID); with one process (or none set) this
    is a no-op so single-host deployments need no special casing. On TPU
    pods the runtime usually auto-detects and the bare initialize() works.
    """
    num = num_processes if num_processes is not None else int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if num <= 1:
        return False
    try:
        # multi-process CPU needs an explicit collectives implementation
        # (jax >= 0.4.34 raises "Multiprocess computations aren't
        # implemented on the CPU backend" without it). Harmless on TPU pods
        # — it only configures the host CPU client — and wrapped for jax
        # versions that renamed/defaulted the option.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # pragma: no cover - option absent on this jax
        pass
    jax.distributed.initialize(  # pragma: no cover - needs a real pod
        coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"),
        num,
        process_id if process_id is not None else int(os.environ.get("JAX_PROCESS_ID", "0")),
    )
    return True


class HostShardPlan(NamedTuple):
    """Which slice of the pod this process owns."""

    n_shards: int
    rows_per_shard: int
    local_device_indices: Tuple[int, ...]  # positions in the mesh's device order
    source_slot: int  # the mesh position this host publishes its batches from

    @property
    def n_local(self) -> int:
        return len(self.local_device_indices)


def host_shard_plan(mesh: Mesh, capacity: int) -> HostShardPlan:
    devs = list(mesh.devices.flat)
    n = len(devs)
    if capacity % n != 0:
        raise ValueError(f"capacity {capacity} not divisible by mesh size {n}")
    me = jax.process_index()
    local = tuple(i for i, d in enumerate(devs) if d.process_index == me)
    if not local:  # pragma: no cover - a host with no mesh devices
        raise ValueError("this process owns no devices in the mesh")
    return HostShardPlan(n, capacity // n, local, local[0])


def build_send_blocks(
    plan: HostShardPlan,
    rows,
    labels,
    elapsed,
    valid,
    *,
    capacity: int,
    batch_per_shard: int,
):
    """This host's contribution to the global exchange: route the local batch
    into per-destination blocks and embed them at this host's source slots.

    Returns ([n_local, n_shards, B] x4 arrays, dropped): every local device
    carries a source slot in the global array; only ``plan.source_slot``'s is
    populated (the others send empty blocks), so one all_to_all moves the
    whole host batch regardless of which device tailed the logs.
    """
    r, l, e, v, dropped = route_batch(
        rows, labels, elapsed, valid,
        capacity=capacity, n_shards=plan.n_shards, batch_per_shard=batch_per_shard,
    )
    nl, ns, B = plan.n_local, plan.n_shards, batch_per_shard
    out_r = np.zeros((nl, ns, B), np.int32)
    out_l = np.zeros((nl, ns, B), np.int32)
    out_e = np.zeros((nl, ns, B), np.float32)
    out_v = np.zeros((nl, ns, B), bool)
    slot = plan.local_device_indices.index(plan.source_slot)
    out_r[slot], out_l[slot], out_e[slot], out_v[slot] = r, l, e, v
    return (out_r, out_l, out_e, out_v), dropped


def place_global(mesh: Mesh, local_arrays):
    """Assemble the per-host send blocks into global arrays sharded on dim 0.

    Single-process: the local arrays already cover every source slot, so a
    sharded device_put suffices. Multi-host: each process contributes only
    its own devices' slices via ``make_array_from_process_local_data``.
    """
    sharding = NamedSharding(mesh, P(SERVICE_AXIS))
    if jax.process_count() == 1:
        return tuple(jax.device_put(a, sharding) for a in local_arrays)
    return tuple(  # pragma: no cover - needs a real pod
        jax.make_array_from_process_local_data(sharding, a) for a in local_arrays
    )


def make_exchange_ingest(mesh: Mesh, cfg: EngineConfig):
    """jit(shard_map(all_to_all + local scatter-ingest)).

    Takes the global ``[n_src, n_dst, B]`` send arrays (dim 0 sharded); after
    the collective each shard ingests the ``[n_src, B]`` records destined for
    it. Row ids inside the blocks are already shard-local (route_batch).
    """
    n = mesh.devices.size
    lcfg = local_config(cfg, n)

    def fn(state: EngineState, rows, labels, elapsed, valid):
        # local block: [1, n_dst, B] (this device's source slot)
        def exchange(x):
            # split my n_dst blocks across peers, concat the n_src received
            # blocks for me: [1, n_dst, B] -> [n_src, 1, B]
            return jax.lax.all_to_all(x, SERVICE_AXIS, split_axis=1, concat_axis=0)

        r = exchange(rows).reshape(-1)
        l = exchange(labels).reshape(-1)
        e = exchange(elapsed).reshape(-1)
        v = exchange(valid).reshape(-1)
        return engine_ingest(state, lcfg, r, l, e, v)

    spec = P(SERVICE_AXIS)
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(_state_specs(cfg), spec, spec, spec, spec),
        out_specs=_state_specs(cfg),
    )
    return jax.jit(mapped, donate_argnums=(0,))
