"""Foundation utilities: config parsing, metrics, binary serialization.

TPU-native counterpart of the reference's src/utils/ module
(config.h, metric.h, io.h). The device-side pieces of src/utils
(thread.h, thread_buffer.h) map to the io prefetcher in cxxnet_tpu.io.
"""

from .config import ConfigIterator, parse_config_string, parse_config_file  # noqa: F401
from .metric import MetricSet, create_metric  # noqa: F401
from . import serializer  # noqa: F401
from . import telemetry  # noqa: F401


# jax's own names: a request is a compile that consulted the cache, a hit
# was loaded from it, a miss was compiled and written to it (compiles
# under jax's size/time thresholds are requests that are neither)
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_cache_counts = {"requests": 0, "hits": 0, "misses": 0}
_cache_listening = False


def _on_cache_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _cache_counts[name] += 1


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already
    uses that directory and the program sets no other; where it is not,
    the cache lives at the fixed ``<repo>/.jax_cache`` (the path is part
    of the cache key, so a directory that moves never hits). Called once
    by every entry point (bin/cxxnet, chip_smoke.py, the tools) before
    the first compile; also starts counting the cache's
    hits and misses for ``compile_cache_stats``."""
    import os
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # jax's 1 s floor sits in the middle of the decode programs'
        # compile times (1-3 s on the chip), so whether a server's next
        # start found them cached was a coin toss
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    global _cache_listening
    if not _cache_listening:    # a second call must not count twice
        jax.monitoring.register_event_listener(_on_cache_event)
        _cache_listening = True
    return d


def compile_cache_stats() -> dict:
    """The persistent cache's directory (None when off) and this
    process's requests, hits and misses since ``enable_compile_cache``."""
    import jax
    return dict(_cache_counts, dir=jax.config.jax_compilation_cache_dir)
