from .api import EngineBase, Request, make_engine, validate_request
from .engine import PagedServeEngine
from .frontend import AudioFrontend, FrontendConfig, synth_samples
from .paged_cache import BlockAllocator, PagedKVCache
from .scheduler import Scheduler, SchedulerConfig

__all__ = [
    "make_engine", "EngineBase", "Request", "validate_request",
    "PagedServeEngine",
    "AudioFrontend", "FrontendConfig", "synth_samples",
    "PagedKVCache", "BlockAllocator",
    "Scheduler", "SchedulerConfig",
]
