"""Persistent XLA compilation cache: restarts pay zero recompiles.

The runtime dispatches one program per pow2 batch width (the AIMD ladder
+ the express lane's small shape), and the engines' scan/while-loop
programs take seconds each to compile.  jax's persistent compilation
cache keys executables by (program, shapes, compile options — device
assignment included, backend) and serves them from disk, so a restarted
scheduler skips every compile it has ever paid in that directory.

Where the directory is, most specific wins:
  1. JAX_COMPILATION_CACHE_DIR, when set: exactly that directory (the
     machine that runs the program decides where its cache lives);
  2. an explicit argument (SchedulerConfig.compile_cache_dir /
     KubeSchedulerConfiguration compileCacheDir / --compile-cache-dir);
  3. DEFAULT_CACHE_DIR, one fixed path inside the checkout (git-ignored).
The literal "off" as the argument disables the cache (when the JAX
variable is not set).  No subdirectory is added: jax's own key already
separates executables compiled for different devices or meshes.

Must run BEFORE the first jit compile to cover it; later calls still
cover every compile after them (jax reads the config per compile).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)

# sentinel accepted by the explicit knob: disables the cache
DISABLED = "off"

# jax.monitoring event names this module listens on.  A program load is
# one _EVENT_COMPILE_SECS duration: jax emits it around
# compiler.compile_or_get_cached, so it covers a backend compile AND a
# persistent-cache hit (whose "/jax/compilation_cache/
# cache_retrieval_time_sec" event times a part of the same interval and
# is therefore not added again)
_EVENT_HIT = "/jax/compilation_cache/cache_hits"
_EVENT_MISS = "/jax/compilation_cache/cache_misses"
_EVENT_COMPILE_SECS = "/jax/core/compile/backend_compile_duration"

_listeners_installed = False
# process-wide program loads so far: [count, seconds] (the scheduler
# folds the delta into phase_seconds["program_load"] per cycle)
_LOADS = [0, 0.0]
_LOADS_LOCK = threading.Lock()


def program_loads() -> Tuple[int, float]:
    """(program loads, their seconds) since the listeners were
    installed: every backend compile or persistent-cache retrieval of an
    executable, in any thread of the process."""
    with _LOADS_LOCK:
        return _LOADS[0], _LOADS[1]


def install_metrics_listeners() -> None:
    """Feed compile-cache hits/misses and cumulative backend-compile
    seconds into the metrics registry (ktpu_compile_cache_events_total,
    ktpu_backend_compile_seconds_total) and the program-load totals
    (program_loads) via jax.monitoring — the telemetry hub
    (runtime/telemetry.py) reads the same counters.  Idempotent."""
    global _listeners_installed
    if _listeners_installed:
        return
    import jax.monitoring as monitoring

    from kubernetes_tpu.utils import metrics as m

    def _on_event(event: str, **kw) -> None:
        if event == _EVENT_HIT:
            m.COMPILE_CACHE_EVENTS.inc(event="hit")
        elif event == _EVENT_MISS:
            m.COMPILE_CACHE_EVENTS.inc(event="miss")

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event == _EVENT_COMPILE_SECS:
            m.COMPILE_SECONDS.inc(float(duration))
            with _LOADS_LOCK:
                _LOADS[0] += 1
                _LOADS[1] += float(duration)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listeners_installed = True


def compile_stats() -> dict:
    """Point-in-time compile telemetry for the hub's samples: cache
    hit/miss counts and cumulative compile seconds (all zero until
    install_metrics_listeners() ran and a compile happened)."""
    from kubernetes_tpu.utils import metrics as m

    return {
        "cache_hits": int(m.COMPILE_CACHE_EVENTS.value(event="hit")),
        "cache_misses": int(m.COMPILE_CACHE_EVENTS.value(event="miss")),
        "compile_seconds": round(float(m.COMPILE_SECONDS.value), 3),
    }


def resolve_cache_dir(cache_dir: Optional[str] = None) -> Optional[str]:
    """The directory the cache will use: JAX_COMPILATION_CACHE_DIR, else
    the explicit argument, else the default.  None/"" argument means "not
    specified here"; "off" disables the cache and returns None."""
    env = os.environ.get(JAX_CACHE_ENV)
    if env:
        return env
    if cache_dir == DISABLED:
        return None
    return cache_dir or DEFAULT_CACHE_DIR


def enable_compile_cache(
    cache_dir: Optional[str] = None,
    min_compile_time_s: float = 0.0,
) -> Optional[str]:
    """Point jax's persistent compilation cache at resolve_cache_dir(...).

    min_compile_time_s=0.0 caches EVERY executable — the runtime's many
    small pow2-width programs are exactly the ones a warm restart wants
    back, and the cold-start acceptance (CI perf_smoke) measures their
    sum.  Idempotent.  Returns the directory in use, or None when
    disabled.
    """
    import jax

    # compile telemetry rides along wherever the cache is configured:
    # the hit/miss counters only mean something once the cache is live
    install_metrics_listeners()
    d = resolve_cache_dir(cache_dir)
    if d is None:
        return None
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_s
    )
    # no size floor: small executables (the express width) must cache
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d
