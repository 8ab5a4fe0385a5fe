from .comm import (ReduceOp, all_gather, all_reduce, all_to_all, axis_index,
                   barrier, broadcast, configure, get_global_device_count,
                   get_local_device_count, get_rank, get_world_size,
                   init_distributed, is_initialized, log_summary, ppermute,
                   reduce_scatter)
from .comms_logging import CommsLogger, get_comms_logger
from .overlap import CollectiveIssue, Ticket

__all__ = [
    "CollectiveIssue", "Ticket",
    "ReduceOp", "all_gather", "all_reduce", "all_to_all", "axis_index",
    "barrier", "broadcast", "configure", "get_global_device_count",
    "get_local_device_count", "get_rank", "get_world_size",
    "init_distributed", "is_initialized", "log_summary", "ppermute",
    "reduce_scatter", "CommsLogger", "get_comms_logger",
]
