"""Pod-scale serving: mesh-sharded engines + disaggregated workers.

Two composable layers over the continuous-batching engine:

- **Layer 1 (SPMD, `pod.mesh`)** — one engine tensor-parallel over a
  device mesh: params sharded by the repo's path-pattern rules, the
  paged KV pool sharded over heads, program out_shardings pinned so the
  compile count stays flat. `sharded_engine(...)` is the factory;
  `EngineConfig(mesh=...)` is the knob it turns.

- **Layer 2 (MPMD, `pod.distributed` / `pod.transfer`)** — prefill and
  decode split into dedicated worker groups shipping KV pages behind the
  ordinary `ServingEngine` API: ONE router, `DistributedPodRouter`
  (alias `PodRouter`), over two transports. `local`: in-process workers
  the router pumps itself — `PodEngine(family, config, params,
  engine_config, PodConfig(...))` builds one (it is
  `build_local_distributed_pod`), deterministic and optionally
  mesh-sharded (layer 1 under layer 2). `socket`: `pod-worker` OS
  processes dialing the router over TCP. Both run the same wire format,
  heartbeats, re-prefill-from-prompt recovery and role bookkeeping;
  `PodConfig` is `DistributedPodConfig`.

Both layers are proven token-exact against the single-device engine on
seeded traces (tier-1, forced-host-device CPU meshes). See
docs/serving.md "Pod-scale serving".
"""

from .mesh import (
    cache_state_shardings,
    shard_params,
    sharded_engine,
    tensor_mesh,
)
from .transfer import KVPageShipment, PageTransport, place_shipment

__all__ = [
    "tensor_mesh",
    "shard_params",
    "cache_state_shardings",
    "sharded_engine",
    "PodConfig",
    "PodRouter",
    "PodEngine",
    "KVPageShipment",
    "PageTransport",
    "place_shipment",
]


# the pod's short names are the one implementation's: no second class
# behind them
_ALIASES = {
    "PodConfig": "DistributedPodConfig",
    "PodRouter": "DistributedPodRouter",
    "PodEngine": "build_local_distributed_pod",
}


def __getattr__(name):
    # the router package is import-heavy (sockets/threads) and optional
    # for layer-1 users — load it lazily on first touch
    target = _ALIASES.get(name, name)
    if target in ("DistributedPodConfig", "DistributedPodRouter",
                  "WorkerHandle", "build_local_distributed_pod"):
        from . import distributed

        return getattr(distributed, target)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
