"""Continuous-batching serving subsystem (slot engine + async front-end).

See docs/serving.md for the slot lifecycle, slot-cache contracts, and the
front-end's queue/deadline/prefix-cache semantics.
"""
from repro.serve.cache import (RecurrentSlotCache, SlotCache, cache_bytes,
                               cache_contract)
from repro.serve.engine import (Completion, Request, ServeEngine,
                                synthetic_trace, percentile_table)
from repro.serve.errors import ERRORS
from repro.serve.frontend import (AsyncServeFrontend, Handle, ServeFrontend,
                                  frontend_table)
from repro.serve.prefix import PrefixCache
from repro.serve.queue import Overloaded, Status
from repro.serve.router import ReplicaRouter, ReplicaState
from repro.serve.scheduler import AdmissionQueue, Scheduler
from repro.serve.sharding import (ServeSharding, device_bytes_estimate,
                                  slot_specs)

__all__ = ["SlotCache", "RecurrentSlotCache", "cache_bytes",
           "cache_contract", "ERRORS", "Request", "Completion",
           "ServeEngine", "synthetic_trace",
           "percentile_table", "ServeFrontend", "AsyncServeFrontend",
           "Handle", "frontend_table", "PrefixCache", "AdmissionQueue",
           "Scheduler", "Overloaded", "Status", "ReplicaRouter",
           "ReplicaState", "ServeSharding", "slot_specs",
           "device_bytes_estimate"]
