"""Continuous-batching serving subsystem over ``InferenceEngineV2``.

No reference analog inside DeepSpeed itself — the reference delegates
this layer to MII's serving loop. Here it is built in: a request
lifecycle (``request.py``), a continuous-batching scheduler with
HCache-aware preemption and restore/decode overlap (``scheduler.py``),
a thread-based frontend with admission control and a deterministic
virtual-clock simulation mode (``server.py``), and serving metrics
emitted through the ``monitor.MonitorMaster`` event path
(``metrics.py``). ``sim.py`` provides a model-free engine double with
the real block-budget arithmetic so the whole policy is CPU-testable.
``crossover.py`` prices restore vs recompute per preempted sequence —
the analytic model the scheduler consults at re-entry. Above all of
that sits the fleet layer: ``router.py`` (KV-pressure- and
prefix-aware placement, per-replica health breakers, migration
planning priced by the crossover's per-link transfer term) and
``fleet.py`` (N replicas sharing one clock, cross-replica migration
with HCache latents as the transfer payload, replica failure domains:
crash/hang/partition, graceful drain, crash recovery).

Two newer layers ride the same machinery: ``spec.py`` (scheduler-
dispatched fused speculative decoding — host-side prompt-lookup
drafting, the engine's ``put_spec`` verify step with per-lane KV
rollback, and the SLO-aware degradation mode driven by TTFT/TPOT
burn) and ``prefix_tree.py`` (the fleet-shared radix prefix tree over
full token-id paths, per-replica warm-prefix caches, and the latent
prefix-broadcast primitive the router prices through ``crossover.py``).
"""

from .autoscale import (AutoscaleConfig, Autoscaler,  # noqa: F401
                        build_autoscale_trace,
                        validate_autoscale_config)
from .clock import MonotonicClock, VirtualClock  # noqa: F401
from .crossover import (CrossoverConfig,  # noqa: F401
                        RestoreCrossoverModel)
from .disagg import (DisaggConfig, DisaggregatedFleet,  # noqa: F401
                     build_mixed_trace, compare_disagg_vs_colocated)
from .fleet import (FleetConfig, FleetReplica,  # noqa: F401
                    Migration, ReplicaRole, ReplicaState,
                    ScaleUpAborted, ServingFleet)
from .metrics import Histogram, ServingMetrics  # noqa: F401
from .prefix_tree import (PrefixReuseConfig,  # noqa: F401
                          RadixPrefixTree, ReplicaPrefixCache,
                          validate_prefix_reuse_config)
from .request import Request, RequestState  # noqa: F401
from .router import (FleetRouter, ReplicaSnapshot,  # noqa: F401
                     RouterConfig)
from .scheduler import (ContinuousBatchingScheduler,  # noqa: F401
                        StepReport)
from .server import (RequestTimeout, ServerConfig,  # noqa: F401
                     ServingServer)
from .sim import SimulatedEngine  # noqa: F401
from .spec import (SLODegradation, SLOModeConfig,  # noqa: F401
                   SpeculationConfig, lookup_draft,
                   validate_slo_mode_config,
                   validate_speculation_config)
