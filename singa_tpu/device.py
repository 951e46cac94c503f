"""Device abstraction for the TPU-native framework.

Capability parity with the reference device layer (reference:
``python/singa/device.py:29-135`` and ``include/singa/core/device.h:57-174``),
re-designed for XLA: a :class:`Device` does not own a memory pool or a stream —
XLA's buffer assignment replaces the reference's Block/DeviceMemPool — but it
keeps the user-visible contract: tensor placement, RNG seeding, graph
(lazy-execution) toggling, synchronisation, and time-profiling verbosity.

The reference's buffered-closure Graph (``src/core/scheduler/scheduler.cc``)
maps onto ``jax.jit`` tracing: ``EnableGraph(True)`` arms tracing mode and
``RunGraph`` replays a compiled XLA executable (see ``singa_tpu/model.py``).
"""

from __future__ import annotations

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Device",
    "CppCPU",
    "TpuDevice",
    "Platform",
    "create_cpu_device",
    "create_tpu_device",
    "create_tpu_devices",
    "create_cuda_gpu",
    "create_cuda_gpus",
    "create_cuda_gpu_on",
    "create_cuda_gpus_on",
    "get_default_device",
    "get_num_tpus",
    "get_num_gpus",
    "device_query",
    "enable_lazy_alloc",
]


class Device:
    """A compute device holding an RNG state and execution-mode flags.

    Mirrors the contract of the reference ``Device`` base class
    (include/singa/core/device.h:57-174): ``SetRandSeed``, ``Sync``,
    ``EnableGraph``/``RunGraph``, verbosity and skip-iteration profiling
    knobs — with XLA semantics underneath.
    """

    _seed_counter = 0
    _lock = threading.Lock()

    def __init__(self, jax_device=None, device_id: int = 0, lang: str = "kCpp"):
        self.id = device_id
        self.lang = lang
        self.jax_device = jax_device
        # Graph/tracing flags (reference device.cc:55-65 buffered mode).
        self.graph_enabled = False
        self.verbosity = 0
        self.skip_iteration = 5
        # Per-device functional RNG (replaces curand generator state).
        with Device._lock:
            Device._seed_counter += 1
            seed = Device._seed_counter
        self._key = jax.device_put(jax.random.PRNGKey(seed), jax_device)
        # Profiling storage filled by model.py when verbosity > 0.
        self.time_profiling = {}

    # ---- RNG ------------------------------------------------------------
    def SetRandSeed(self, seed: int) -> None:
        self._key = jax.device_put(jax.random.PRNGKey(int(seed)),
                                   self.jax_device)

    def set_rand_seed(self, seed: int) -> None:
        self.SetRandSeed(seed)

    def _heal_key(self):
        """Self-heal if a traced consumer leaked its in-trace key into this
        host-side state (the stored key would be a dead tracer): hops to
        a fresh per-device stream (device identity + leak counter), placed
        where a seeded key is: a compiled step given a key of another
        placement than the one it hands back compiles a second
        executable."""
        if isinstance(self._key, jax.core.Tracer) and \
                not isinstance(jnp.zeros(()), jax.core.Tracer):
            self._leaks = getattr(self, "_leaks", 0) + 1
            self._key = jax.device_put(jax.random.fold_in(
                jax.random.PRNGKey(id(self) & 0x7fffffff),
                0x5eed + self._leaks), self.jax_device)

    def rand_key(self):
        """Split and return a fresh PRNG key (functional curand
        equivalent)."""
        self._heal_key()
        self._key, sub = jax.random.split(self._key)
        return sub

    def current_key(self):
        """The current key WITHOUT splitting — for consumers that advance
        the stream themselves (the compiled train step splits in-trace and
        hands the next key back, avoiding a host-side split per step)."""
        self._heal_key()
        return self._key

    # rng state threading for jit (model.py swaps these in/out of the trace)
    def _get_rng_state(self):
        return self._key

    def _set_rng_state(self, key):
        self._key = key

    # ---- Execution mode -------------------------------------------------
    def EnableGraph(self, enable: bool) -> None:
        self.graph_enabled = bool(enable)

    def RunGraph(self, sequential: bool = False) -> None:
        # Execution of the compiled step is driven by Model; kept for API
        # parity with reference device.cc:67-82 (a no-op at device level).
        pass

    def ResetGraph(self) -> None:
        pass

    def _record_time(self, name: str, seconds: float) -> None:
        """Accumulate a timing sample (count, total seconds) under a name.
        Sample sources: whole compiled steps at verbosity>=1, per-op
        fwd/bwd at verbosity>=2 (reference per-node cudaEvent timing,
        src/core/device/cuda_gpu.cc:117, scheduler.cc:240-298)."""
        rec = self.time_profiling.setdefault(name, [0, 0.0])
        rec[0] += 1
        rec[1] += seconds

    def PrintTimeProfiling(self) -> None:
        """Print the aggregated timing table (reference
        Graph::PrintTimeProfiling, src/core/scheduler/scheduler.cc:240-298:
        verbosity 1 = whole step, verbosity 2 = per-op rows)."""
        if not self.time_profiling:
            print("No time profiling data collected; "
                  "set verbosity>0 and run model steps.")
            return
        rows = sorted(self.time_profiling.items(),
                      key=lambda kv: -kv[1][1])
        width = max(len(k) for k, _ in rows)
        print(f"  {'op':<{width}}  {'calls':>6}  {'total ms':>10}  "
              f"{'avg ms':>9}")
        for name, (count, total) in rows:
            avg = total / count if count else 0.0
            print(f"  {name:<{width}}  {count:>6}  {total * 1e3:>10.3f}  "
                  f"{avg * 1e3:>9.3f}")

    def ResetTimeProfiling(self) -> None:
        self.time_profiling = {}

    def SetVerbosity(self, verbosity: int) -> None:
        """0 = off; 1 = whole-step wall times (after skip_iteration);
        2 = per-op times + static cost analysis + a one-time MEASURED
        per-fusion profile of the compiled step.

        NOTE: verbosity>=2 forces the FIRST graph-mode train call to run
        eagerly (per-op wall times only exist op-by-op), skipping the
        zero-compute abstract rehearsal. That eager pass is one device
        dispatch per op, which is slow on a big model — profile small,
        or at verbosity 1."""
        self.verbosity = int(verbosity)

    def SetSkipIteration(self, skip: int) -> None:
        self.skip_iteration = int(skip)

    # ---- Sync / placement ----------------------------------------------
    def Sync(self) -> None:
        """Block until all queued work on this device is done."""
        (jnp.zeros((), device=self.jax_device) + 0).block_until_ready()

    def put(self, array):
        """Place a host array on this device; returns a jax.Array."""
        return jax.device_put(jnp.asarray(array), self.jax_device)

    def name(self) -> str:
        return f"{type(self).__name__}({self.id})"

    def __repr__(self) -> str:
        return f"<{self.name()} lang={self.lang} platform=" \
               f"{getattr(self.jax_device, 'platform', '?')}>"


class CppCPU(Device):
    """Host CPU device (reference src/core/device/cpp_cpu.cc)."""

    def __init__(self, device_id: int = 0):
        # local (addressable) devices only: under a multi-process
        # jax.distributed mesh, jax.devices() lists other hosts' devices,
        # which this process cannot allocate on
        cpus = [d for d in jax.local_devices() if d.platform == "cpu"]
        if not cpus:
            try:
                cpus = jax.local_devices(backend="cpu")
            except RuntimeError:
                cpus = jax.devices("cpu")   # single-process: all local
        super().__init__(cpus[0], device_id, lang="kCpp")


class TpuDevice(Device):
    """TPU device — the peer of the reference's CudaGPU
    (src/core/device/cuda_gpu.cc), with XLA replacing cuDNN/cuBLAS/cnmem.
    Like the reference's ``create_cuda_gpu`` without a GPU, it raises
    when this process has no accelerator: ask for ``create_cpu_device``
    to run on the host."""

    def __init__(self, device_id: int = 0, jax_device=None):
        if jax_device is None:
            accel = [d for d in jax.local_devices() if d.platform != "cpu"]
            if not accel:
                raise RuntimeError(
                    "no accelerator: jax.local_devices() lists only "
                    f"{jax.default_backend()!r} devices. Use "
                    "device.create_cpu_device() to run on the host.")
            jax_device = accel[device_id % len(accel)]
        super().__init__(jax_device, device_id, lang="kTpu")


class Platform:
    """Device discovery/factory (reference src/core/device/platform.cc)."""

    @staticmethod
    def GetNumGPUs() -> int:
        return len([d for d in jax.devices() if d.platform != "cpu"])

    @staticmethod
    def DeviceQuery(device_id: int = 0, verbose: bool = False) -> str:
        devs = jax.devices()
        if device_id >= len(devs):
            return f"no device {device_id}"
        d = devs[device_id]
        info = (f"Device {device_id}: platform={d.platform} "
                f"kind={getattr(d, 'device_kind', '?')} "
                f"process={d.process_index}")
        if verbose:
            print(info)
        return info

    @staticmethod
    def CreateTpuDevices(num: int):
        return [TpuDevice(i) for i in range(num)]


_default_device = None
_lock = threading.Lock()


def get_default_device() -> Device:
    """Default host device (reference python/singa/device.py:121-128)."""
    global _default_device
    with _lock:
        if _default_device is None:
            _default_device = CppCPU()
    return _default_device


def create_cpu_device() -> Device:
    return CppCPU()


def create_tpu_device(device_id: int = 0) -> TpuDevice:
    return TpuDevice(device_id)


def create_tpu_devices(num: int):
    return [TpuDevice(i) for i in range(num)]


# CUDA-named aliases for drop-in compatibility with reference scripts
# (python/singa/device.py:60-118): they return the accelerator present
# and, like the reference without a GPU, raise when there is none.
def create_cuda_gpu(set_default=True):  # noqa: ARG001 (parity signature)
    return create_tpu_device(0)


def create_cuda_gpu_on(device_id: int):
    return create_tpu_device(device_id)


def create_cuda_gpus(num: int):
    return create_tpu_devices(num)


def create_cuda_gpus_on(device_ids):
    return [create_tpu_device(i) for i in device_ids]


def get_num_tpus() -> int:
    return len([d for d in jax.devices() if d.platform == "tpu"])


def get_num_gpus() -> int:
    # parity alias: number of accelerators visible
    return Platform.GetNumGPUs()


def device_query(device_id: int = 0, verbose: bool = False) -> str:
    return Platform.DeviceQuery(device_id, verbose)


def enable_lazy_alloc(enable: bool) -> None:
    """Parity no-op: XLA always allocates lazily at compile/execute time
    (reference lazy_alloc_ src/core/device/device.cc:23)."""
    _ = enable
