"""Where XLA's persistent compilation cache lives.

A cold start on the chip compiles for minutes; the persistent cache
turns the second start into a file read. The directory is part of the
deployment, not of the code:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it at import and nothing
  here touches the directory: the cache is wherever the operator put it.
* unset — one fixed path inside the checkout (``<repo>/.jax_cache``,
  git-ignored). Fixed on purpose: the directory is part of the cache
  key, so a temp name, pid or timestamp would never hit.

:func:`enable` is idempotent and cheap; every entry point that compiles
(``launch``, ``make_step``, ``InferenceEngine``) calls it before its
first compile.

Donation and the cache: on jax 0.4.37 a deserialized CPU executable lost
its input-output aliasing and a donated train step diverged. Under the
installed jax 0.9.0 a warm run (every program a cache hit) reproduces
the cold run's losses bit for bit with donation on — CPU, 1 and 8
devices, and the TPU v5e (chip_smoke.py run twice against one directory,
PR 21) — so the cache is on everywhere.

The module also keeps the process-wide count of what XLA built
(:func:`compile_events`): JAX tells a listener after every program it
compiled or loaded from the persistent cache, whoever asked for it —
the trace-time counters of the step builder and the serving pools see
only their own programs, not ``submit()``'s ``jax.random.split`` or a
per-shape slice.
"""

from __future__ import annotations

import os
import threading

import jax

from ..obs import trace as _dpxtrace
from . import env

#: The in-checkout default (three levels up: runtime/ -> package -> repo).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Make sure the persistent compilation cache has a directory and
    return it. Obeys ``JAX_COMPILATION_CACHE_DIR`` when set (JAX's own
    handling places the cache; no directory is set from code), otherwise
    points JAX at :data:`DEFAULT_DIR`."""
    _listen()
    # an executable carries the op names it was compiled with (the
    # program's jax.named_scopes, which profiler traces are read by), and
    # JAX leaves them out of the cache key by default: a cache shared with
    # another commit would hand back that commit's names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if env.raw("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


# -- what XLA built, process-wide ---------------------------------------------

#: Fires once for every program built, AFTER the fact, on the thread that
#: asked — around a real compile and around a persistent-cache load alike
#: (not on a call of an already-built program).
_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
#: Fires inside the former, just before it, when the cache had the program.
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_events = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
           "cache_load_s": 0.0}
_events_lock = threading.Lock()
_loaded = threading.local()      # this thread's build was a cache load
_listening = False


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _CACHE_LOAD_EVENT:
        _loaded.hit = True
        return
    if event != _BUILD_EVENT:
        return
    cached = getattr(_loaded, "hit", False)
    _loaded.hit = False
    with _events_lock:
        if cached:
            _events["cache_hits"] += 1
            _events["cache_load_s"] += secs
        else:
            _events["compiles"] += 1
            _events["compile_s"] += secs
    # told after the fact, on the thread that built: in a profiler trace
    # the zero-length mark falls inside the span that caused the build
    with _dpxtrace.span("xla.compile", secs=round(secs, 6),
                        cached=int(cached)):
        pass


def _listen() -> None:
    global _listening
    with _events_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_events() -> dict:
    """``{"compiles", "compile_s", "cache_hits", "cache_load_s"}`` of
    this process so far: programs XLA compiled and the seconds that took,
    programs loaded from the persistent cache and the seconds that took.
    Counts from the first :func:`enable` (or the first call of this) on."""
    _listen()
    with _events_lock:
        return dict(_events)
