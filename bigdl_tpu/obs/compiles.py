"""The compile counter and span the program owns.

ONE `jax.monitoring` listener pair for the process (registered when
`bigdl_tpu.obs` is imported, never per engine or per optimizer). Per
backend compile — JAX's `backend_compile_duration` event, which fires
for every executable it builds OR loads from the persistent cache — it

* increments `xla_compiles_total{cache="hit"|"miss"}` in the active
  registry, so the scrape endpoint shows recompiles on a live fleet
  (`hit` = loaded from the persistent cache, `miss` = compiled);
* records a `compile` span `(now - duration, now)` on the active
  tracer, so an idle gap of the device under a recompile reads
  `compile` instead of whatever request-long span covers it.

Host-side by construction: the listener receives a name and a float.
Both honor `obs.enabled()`; the span also needs the tracer enabled.
"""

from __future__ import annotations

import threading

__all__ = ["install_compile_listener"]

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_local = threading.local()      # a hit is announced before its duration
_installed = False


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _local.hit = True


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != _BACKEND_COMPILE:
        return
    cache = "hit" if getattr(_local, "hit", False) else "miss"
    _local.hit = False
    from bigdl_tpu import obs

    if not obs.enabled():
        return
    family = obs.get_registry().counter(
        "xla_compiles_total",
        "backend compiles: loaded from the persistent cache (hit) or "
        "compiled (miss)", labelnames=("cache",))
    # what this process had to build depends on its caches, not on the
    # run's inputs: scraped, but kept out of flight-recorder bundles
    family.process_state = True
    family.labels(cache=cache).inc()
    tracer = obs.get_tracer()
    if tracer.enabled:
        now = tracer.now()
        tracer.complete("compile", "xla", now - duration, now,
                        args={"cache": cache,
                              "fun": str(kwargs.get("fun_name", ""))})


def install_compile_listener() -> None:
    """Idempotent; registering touches no backend."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True
