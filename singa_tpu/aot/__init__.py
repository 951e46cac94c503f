"""Cold-start elimination: durable, verified compiled programs.

Every elastic restart (the exit-75 path), rescale, and serving-replica
spin-up used to pay a full XLA recompile. This subsystem makes compiled
programs **durable artifacts** with a strict honored-or-refused
contract:

- :mod:`.cache` — JAX's persistent compilation cache behind ONE policy
  object (:class:`~singa_tpu.aot.cache.CachePolicy`: size budget with
  LRU GC, enable/disable) and ONE directory rule
  (``JAX_COMPILATION_CACHE_DIR`` when set, else the directory asked
  for, else ``<checkout>/.jax_compile_cache``), wired through
  ``Model.compile`` and ``Model.compile_serving`` (``compile_cache=``)
  and installed by every entry point. Hits and misses
  are counted (``compile_cache_hits_total`` / ``_misses_total``) and
  every traced dispatch's ``compile_seconds`` observation carries a
  ``source="cache"|"fresh"`` label, so the win is visible in telemetry
  instead of inferred from wall clocks.
- :mod:`.manifest` — the refusal side: every exported artifact carries
  a manifest recording jax/jaxlib versions, backend + topology, the
  arg avals and donation layout, the precision/quant policy stamp, and
  a ``crc32`` content digest. :func:`~singa_tpu.aot.manifest.verify`
  raises a typed :class:`~singa_tpu.aot.manifest.AotMismatch` NAMING
  the first failed axis — a mismatched artifact falls back to a loud
  fresh compile and is quarantined, never silently executed.
- :mod:`.export` — the durability side:
  :class:`~singa_tpu.aot.export.AotStore` serializes lowered+compiled
  executables (``jax.experimental.serialize_executable``) into an
  ``aot/`` sidecar beside the checkpoints (same sidecar discipline as
  ``data_state/``; scrubbed by ``CheckpointManager.scrub`` and
  ``tools/scrub_checkpoints.py``). ``ResilientTrainer(aot=True)``
  exports the train step after the first step and a restarted worker
  deserializes it instead of retracing;
  ``compile_serving(aot_store=...)`` does the same for the serving
  prefill/decode programs — a warm restart re-steps / re-serves in
  seconds with ``n_traces`` still 1 and ZERO
  ``compile_seconds{source="fresh"}`` observations (the chaos
  ``warm-restart`` gate).

``tools/aot_cache.py`` is the operator CLI (prebuild / inspect / gc /
scrub / ``--selftest``).
"""

from .cache import CachePolicy, install, snapshot  # noqa: F401
from .export import AotStore, export_serving, export_train_step  # noqa: F401
from .manifest import AotMismatch  # noqa: F401

__all__ = ["CachePolicy", "install", "snapshot", "AotStore",
           "export_train_step", "export_serving", "AotMismatch"]
