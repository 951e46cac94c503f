"""The persistent-compile-cache policy: one object, one directory rule.

JAX's persistent compilation cache turns a recompile of an
already-seen program into a disk read, but its raw form is a scatter
of config flags with no observability and no size control. This module
fronts it with ONE policy object and ONE rule for where the cache
lives, used by every entry point (``chip_smoke.py``, ``bench.py``, the
examples, the trainer, ``Model.compile(compile_cache=...)`` /
``Model.compile_serving(compile_cache=...)``):

- ``JAX_COMPILATION_CACHE_DIR`` set: the cache is that directory. jax
  reads the variable itself at import, so nothing here sets a
  directory in code and a directory asked for in code is not used —
  whoever runs the program places the cache from outside.
- unset: the cache is the directory asked for (``CachePolicy(dir)`` /
  ``compile_cache="/path"``), else ``<checkout>/.jax_compile_cache``
  (:func:`default_dir`) — a fixed path, because the path is part of
  the cache key and a directory that moves never hits.

What installing buys beyond the raw flags, attached to whichever
directory the rule picked:

- **hit/miss counters** — a process-wide ``jax.monitoring`` listener
  counts cache hits and misses into
  ``compile_cache_hits_total`` / ``compile_cache_misses_total`` on the
  metrics registry (and a host-side snapshot for cheap deltas), so
  every traced dispatch can label its ``compile_seconds`` observation
  ``source="cache"`` or ``source="fresh"``
  (:func:`classify`) — the cold-start win is a dashboard fact, not an
  inference from wall clocks;
- **size budget with LRU GC** — :func:`gc` prunes the cache directory
  least-recently-used-first down to ``size_budget_bytes`` (JAX writes
  an ``-atime`` companion per entry exactly for this), run at install
  and on demand (``tools/aot_cache.py gc``);
- **enable/disable** — one switch, not four flags.
"""

from __future__ import annotations

import copy
import os
import threading

from jax import monitoring

from ..observability import metrics as _metrics

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_EVT_HIT = "/jax/compilation_cache/cache_hits"
_EVT_MISS = "/jax/compilation_cache/cache_misses"

_LOCK = threading.Lock()
_ACTIVE = None                    # the installed CachePolicy (or None)
_LISTENING = False
# monotonically-increasing host counters the listener feeds; snapshot()
# hands out copies so dispatch sites can diff around a call
_COUNTS = {"hits": 0, "misses": 0}


class CachePolicy:
    """Persistent-compile-cache configuration (see module docstring).

    - ``directory``: where XLA executables persist when
      ``JAX_COMPILATION_CACHE_DIR`` is unset; None = :func:`default_dir`.
      The policy :func:`install` returns carries the directory in effect.
    - ``enabled``: False turns the cache OFF at install (the one-switch
      opt-out).
    - ``size_budget_bytes``: LRU GC target; None = unbounded.
    - ``min_compile_seconds`` / ``min_entry_bytes``: JAX's write
      thresholds. The defaults (0 / -1) cache EVERYTHING including
      tiny CPU programs — cold-start elimination wants the whole
      program set warm, not just the expensive tail.
    """

    def __init__(self, directory=None, *, enabled=True,
                 size_budget_bytes=None, min_compile_seconds=0.0,
                 min_entry_bytes=-1):
        self.directory = None if directory is None \
            else os.path.abspath(os.fspath(directory))
        self.enabled = bool(enabled)
        self.size_budget_bytes = None if size_budget_bytes is None \
            else int(size_budget_bytes)
        self.min_compile_seconds = float(min_compile_seconds)
        self.min_entry_bytes = int(min_entry_bytes)

    def describe(self):
        return {"directory": self.directory, "enabled": self.enabled,
                "size_budget_bytes": self.size_budget_bytes,
                "min_compile_seconds": self.min_compile_seconds,
                "min_entry_bytes": self.min_entry_bytes}

    def __repr__(self):
        return f"CachePolicy({self.describe()!r})"


def _listener(event, **kw):
    if event == _EVT_HIT:
        _COUNTS["hits"] += 1
        _metrics.default_registry().counter(
            "compile_cache_hits_total",
            "XLA compiles served from the persistent cache").inc()
    elif event == _EVT_MISS:
        _COUNTS["misses"] += 1
        _metrics.default_registry().counter(
            "compile_cache_misses_total",
            "XLA compiles the persistent cache could not serve"
        ).inc()


def _ensure_listener():
    global _LISTENING
    with _LOCK:
        if not _LISTENING:
            monitoring.register_event_listener(_listener)
            _LISTENING = True


def resolve(policy):
    """Coerce a user-facing ``compile_cache=`` value to a
    :class:`CachePolicy`: a policy passes through, a path string/
    PathLike becomes an enabled policy over it, ``True``/``None`` an
    enabled one over the rule's directory, ``False`` a disabled one."""
    if isinstance(policy, CachePolicy):
        return policy
    if policy is False:
        return CachePolicy(enabled=False)
    if policy is True or policy is None:
        return CachePolicy()
    return CachePolicy(policy)


def default_dir():
    """``<checkout>/.jax_compile_cache``: beside the ``singa_tpu``
    package, listed in ``.gitignore``, and the same path on every run
    from this checkout."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_compile_cache")


def effective_dir(directory=None):
    """(directory, source) the rule picks: the environment's when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else ``directory``, else
    :func:`default_dir`."""
    env = os.environ.get(ENV_DIR)
    if env:
        return os.path.abspath(env), "env"
    if directory is not None:
        return os.path.abspath(os.fspath(directory)), "explicit"
    return default_dir(), "default"


def install(policy=None):
    """Install ``policy`` (a :class:`CachePolicy`, a directory, True/
    None for the rule's directory, or False to disable) process-wide:
    point jax's persistent compilation cache at the directory the rule
    picks (module docstring), set the write thresholds, register the
    hit/miss listener, and GC down to the size budget. Returns the
    active policy, whose ``directory`` is the one in effect."""
    global _ACTIVE
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as _cc)
    pol = copy.copy(resolve(policy))    # the caller's object stays as is
    pol.directory, source = effective_dir(pol.directory)
    jax.config.update("jax_enable_compilation_cache", pol.enabled)
    if pol.enabled:
        os.makedirs(pol.directory, exist_ok=True)
        if source != "env":
            jax.config.update("jax_compilation_cache_dir", pol.directory)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          pol.min_compile_seconds)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          pol.min_entry_bytes)
        _ensure_listener()
        if pol.size_budget_bytes is not None:
            gc(pol)
    # jax memoizes "is the cache in use" once per task at its first
    # compile; reset_cache drops that memo so installing mid-process
    # takes effect
    _cc.reset_cache()
    _ACTIVE = pol
    return pol


def active():
    """The installed :class:`CachePolicy`, or None."""
    return _ACTIVE


def uninstall():
    """Undo :func:`install` (tests, or a one-shot tool that must not
    leave process-global config behind): a directory set in code is
    cleared, one that came from the environment is left to jax's own
    handling. The hit/miss listener stays registered."""
    global _ACTIVE
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as _cc)
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_enable_compilation_cache", True)
    _cc.reset_cache()
    _ACTIVE = None


def snapshot():
    """Copy of the host-side hit/miss counters — take one BEFORE a
    dispatch that may compile, then :func:`classify` after."""
    return dict(_COUNTS)


def classify(before):
    """Label the compile(s) that happened since ``before`` (a
    :func:`snapshot`): ``"cache"`` when every new compilation was
    served from the persistent cache, ``"fresh"`` otherwise —
    including when no cache is installed (no events fire, so nothing
    can prove a hit)."""
    hits = _COUNTS["hits"] - before.get("hits", 0)
    misses = _COUNTS["misses"] - before.get("misses", 0)
    return "cache" if hits > 0 and misses == 0 else "fresh"


def stats(directory=None):
    """{entries, bytes} of a cache directory (the active policy's when
    None). Missing directory counts as empty."""
    d = directory if directory is not None else \
        (_ACTIVE.directory if _ACTIVE is not None else effective_dir()[0])
    entries = 0
    total = 0
    try:
        names = os.listdir(d)
    except OSError:
        names = []
    for n in names:
        path = os.path.join(d, n)
        try:
            size = os.path.getsize(path)
        except OSError:
            continue
        total += size
        if n.endswith("-cache"):
            entries += 1
    return {"directory": os.path.abspath(str(d)), "entries": entries,
            "bytes": total}


def gc(policy=None, *, budget_bytes=None):
    """LRU garbage collection: delete least-recently-used cache
    entries until the directory fits the budget (the policy's
    ``size_budget_bytes`` unless overridden). Recency comes from each
    entry's ``-atime`` companion file (written by jax on every cache
    read precisely so external GC can be LRU); an entry without one
    falls back to the cache file's own mtime. Returns a report dict;
    never raises."""
    pol = policy if policy is not None else _ACTIVE
    if pol is None and budget_bytes is None:
        return {"removed": 0, "bytes_freed": 0, "entries": 0,
                "bytes": 0}
    directory = (pol.directory if pol is not None else None) \
        or effective_dir()[0]
    budget = budget_bytes if budget_bytes is not None \
        else getattr(pol, "size_budget_bytes", None)
    try:
        names = os.listdir(directory)
    except OSError:
        return {"removed": 0, "bytes_freed": 0, "entries": 0,
                "bytes": 0}
    entries = []        # (last_use, total_bytes, [paths])
    total = 0
    for n in names:
        if not n.endswith("-cache"):
            continue
        path = os.path.join(directory, n)
        atime_path = os.path.join(directory, n[:-len("-cache")]
                                  + "-atime")
        try:
            size = os.path.getsize(path)
        except OSError:
            continue
        try:
            last_use = os.path.getmtime(atime_path)
            size += os.path.getsize(atime_path)
        except OSError:
            atime_path = None
            last_use = os.path.getmtime(path)
        total += size
        entries.append((last_use, size, [p for p in (path, atime_path)
                                         if p]))
    removed = 0
    freed = 0
    if budget is not None:
        entries.sort()                      # oldest last-use first
        over = total - int(budget)
        for _t, size, paths in entries:
            if over <= 0:
                break
            for p in paths:
                try:
                    os.remove(p)
                except OSError:
                    pass
            over -= size
            freed += size
            removed += 1
    return {"removed": removed, "bytes_freed": freed,
            "entries": len(entries) - removed, "bytes": total - freed}


__all__ = ["CachePolicy", "install", "active", "uninstall", "resolve",
           "snapshot", "classify", "stats", "gc", "default_dir",
           "effective_dir", "ENV_DIR"]
