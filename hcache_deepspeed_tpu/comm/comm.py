"""Collective-communication facade.

Reference analog: ``deepspeed/comm/comm.py`` (808 LoC) — a
torch.distributed-signature facade over a pluggable ``Backend``
(``comm/backend.py:25``), with ``init_distributed`` (:636) doing rendezvous
and env discovery, every collective wrapped in ``@timed_op`` for logging, and
capability probes with chunked fallbacks (:252-333).

TPU-native re-design:

* **Rendezvous** → ``jax.distributed.initialize()`` (one controller process
  per host; chips inside a process need no rendezvous at all). Env discovery
  keeps the reference's spirit: explicit args > ``HDS_*``/torch-style env
  vars > cloud TPU metadata auto-detection (handled inside jax).
* **Collectives** → thin wrappers over ``jax.lax`` ops on *named mesh axes*.
  A "process group" argument becomes an axis name (or tuple of axis names)
  of the global mesh — see ``parallel/topology.py``. These wrappers are
  traced into jitted programs; XLA chooses ICI/DCN routing and fuses/combines
  (the reference's coalescing manager and `has_all_gather_into_tensor`
  fallback machinery have no equivalent because XLA always provides the
  fused form).
* **Logging** → trace-time size/op recording via ``CommsLogger`` plus XLA
  profiler ranges, replacing host-side ``@timed_op`` timing.

These functions must be called inside a ``shard_map``/``pjit`` context where
the named axes are bound (like the reference's requirement that
``init_process_group`` precede collective calls).
"""

import os

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.logging import logger
from .comms_logging import get_comms_logger

_initialized = False


# ------------------------------------------------------------------ #
# Reduce ops (reference: deepspeed/comm/reduce_op.py mirrors torch)
# ------------------------------------------------------------------ #
class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "product"


def _normalize_axes(group):
    """A 'group' is a mesh-axis name or tuple of names. None = all axes of
    the current shard_map context is not expressible; require explicit."""
    if group is None:
        raise ValueError(
            "group=None: pass a mesh axis name (e.g. 'data') or tuple; on "
            "TPU the named mesh axis *is* the process group")
    if isinstance(group, str):
        return (group,)
    return tuple(group)


def _log(op_name, x, axes):
    try:
        size = x.size * x.dtype.itemsize
    except Exception:
        size = 0
    get_comms_logger().append(op_name, axes, size)


# ------------------------------------------------------------------ #
# Rendezvous / process bootstrap
# ------------------------------------------------------------------ #
def init_distributed(dist_backend=None,
                     auto_mpi_discovery=True,
                     init_method=None,
                     rank=-1,
                     world_size=-1,
                     timeout=None,
                     coordinator_address=None):
    """Bootstrap multi-host execution.

    Reference: ``comm/comm.py:636 init_distributed`` (+ mpi/AML/SageMaker env
    discovery :705-808). Here rendezvous is only needed across *hosts*;
    single-host (even 256-chip single-slice via one controller) needs nothing.
    Multi-host is asked for by name — a coordinator address or a process
    count above one, as arguments or through the launcher's ``HDS_*``
    variables — never inferred from what a TPU host's environment happens
    to carry, and a rendezvous that was asked for and fails raises.
    Safe to call multiple times.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "HDS_COORDINATOR_ADDRESS")
    num_processes = world_size if world_size > 0 else _env_int(
        "HDS_NUM_PROCESSES", _env_int("WORLD_SIZE", -1))
    process_id = rank if rank >= 0 else _env_int(
        "HDS_PROCESS_ID", _env_int("RANK", -1))

    if coordinator_address or num_processes > 1:
        kwargs = {}
        if coordinator_address:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes > 0:
            kwargs["num_processes"] = num_processes
        if process_id >= 0:
            kwargs["process_id"] = process_id
        if _platform_is_cpu():
            # Cross-process collectives on the CPU backend need a
            # transport (TPU rides ICI/DCN natively); gloo is jax's
            # built-in one. The reference's analog is the CCL backend
            # for CPU runs (SURVEY §2.2). Must be set before backends
            # initialise.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        logger.info(f"jax.distributed.initialize({kwargs})")
        jax.distributed.initialize(**kwargs)
    _initialized = True


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _platform_is_cpu():
    """True when jax may run on the cpu backend — decided WITHOUT
    touching jax.devices()/default_backend(), which would initialise the
    backend and foreclose jax.distributed.initialize(). Unset platform
    counts as cpu (jax falls back to cpu when no accelerator is found,
    and the gloo knob is harmless on TPU)."""
    cfg = getattr(jax.config, "jax_platforms", None)
    platforms = cfg or os.environ.get("JAX_PLATFORMS", "")
    first = platforms.split(",")[0].strip().lower()
    return first in ("", "cpu")


def is_initialized():
    return _initialized


def get_rank():
    return jax.process_index()


def get_world_size():
    return jax.process_count()


def get_local_device_count():
    return jax.local_device_count()


def get_global_device_count():
    return jax.device_count()


def barrier():
    """Host-level barrier across all processes."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("hds_barrier")


# ------------------------------------------------------------------ #
# In-program collectives (called under shard_map over the global mesh)
# ------------------------------------------------------------------ #
def all_reduce(x, op=ReduceOp.SUM, group=None):
    """Reference: comm.py:221 all_reduce → here lax.p* on mesh axes."""
    axes = _normalize_axes(group)
    _log("all_reduce", x, axes)
    if op == ReduceOp.SUM:
        return lax.psum(x, axes)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axes)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axes)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axes)
    if op == ReduceOp.PRODUCT:
        # no native pprod; exp/log trick is unstable — use allgather+prod
        g = lax.all_gather(x, axes)
        return jnp.prod(g, axis=0)
    raise ValueError(f"unknown reduce op {op}")


def all_gather(x, group=None, axis=0, tiled=True):
    """Reference: all_gather_into_tensor (comm.py:252). ``tiled=True``
    concatenates along ``axis`` (torch semantics); False stacks a new dim."""
    axes = _normalize_axes(group)
    _log("all_gather", x, axes)
    return lax.all_gather(x, axes, axis=axis, tiled=tiled)


def reduce_scatter(x, op=ReduceOp.SUM, group=None, scatter_dimension=0):
    """Reference: reduce_scatter_tensor (comm.py:289)."""
    axes = _normalize_axes(group)
    _log("reduce_scatter", x, axes)
    assert op in (ReduceOp.SUM, ReduceOp.AVG)
    out = lax.psum_scatter(x, axes, scatter_dimension=scatter_dimension,
                           tiled=True)
    if op == ReduceOp.AVG:
        out = out / _group_size(axes)
    return out


def all_to_all(x, group=None, split_axis=0, concat_axis=0):
    """Reference: all_to_all_single (comm.py:351); backbone of Ulysses and
    MoE dispatch."""
    axes = _normalize_axes(group)
    _log("all_to_all", x, axes)
    if len(axes) != 1:
        raise ValueError("all_to_all runs over exactly one mesh axis")
    return lax.all_to_all(x, axes[0], split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ppermute(x, perm, group=None):
    """Point-to-point ring shift (reference: pipeline p2p send/recv,
    ``runtime/pipe/p2p.py`` — TPU-native form is a collective permute)."""
    axes = _normalize_axes(group)
    _log("ppermute", x, axes)
    if len(axes) != 1:
        raise ValueError("ppermute runs over exactly one mesh axis")
    return lax.ppermute(x, axes[0], perm)


def broadcast(x, src=0, group=None):
    """Broadcast from mesh-coordinate ``src`` along ``group`` axes."""
    axes = _normalize_axes(group)
    _log("broadcast", x, axes)
    if len(axes) != 1:
        raise ValueError("broadcast runs over one mesh axis")
    ax = axes[0]
    idx = lax.axis_index(ax)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, ax)


def axis_index(group):
    axes = _normalize_axes(group)
    if len(axes) == 1:
        return lax.axis_index(axes[0])
    # row-major linearised index over multiple axes
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _group_size(axes):
    size = 1
    for a in axes:
        size *= lax.axis_size(a)
    return size


def get_group_size(group):
    """Static group size from the installed topology (host-side)."""
    from ..parallel.topology import get_topology
    topo = get_topology()
    return int(jnp.prod(jnp.array(
        [topo.axis_size(a) for a in _normalize_axes(group)])))


def log_summary(monitor=None, step=0):
    """Reference: ``dist.log_summary()`` (comm/comm.py:428) — prints the
    aggregate op → count/volume table; with ``monitor`` the same
    aggregate also rides ``MonitorMaster.write_events`` so comm volume
    lands beside the step metrics."""
    get_comms_logger().log_summary(monitor=monitor, step=step)


def configure(enabled=None, verbose=None, prof_all=None, prof_ops=None,
              debug=None):
    get_comms_logger().configure(enabled, verbose, prof_all, prof_ops,
                                 debug)
