"""CPU runtime configuration: host-device setup, worker pinning, env hygiene.

This is the one place that touches process-level CPU execution state, used
by every entrypoint that wants multi-core execution:

* :func:`configure_cpu_devices` — expose ``n`` host cores as JAX devices
  (``--xla_force_host_platform_device_count=n``) by *merging* into any
  existing ``XLA_FLAGS`` instead of clobbering it.  Must run before the
  first JAX backend use; warns (never fails) when ``n`` oversubscribes
  the host.  It affects only the CPU backend: TPU chips are selected
  from ``jax.devices()``.  ``launch/dryrun.py`` / ``launch/perf.py``
  route their 512 placeholder devices through here, and
  ``benchmarks/scaling_cores.py`` / ``launch/serve.py --devices`` use it
  for real data-parallel meshes.
* :func:`maybe_pin` — pin the calling *thread* to a CPU set
  (``sched_setaffinity``, the ``taskset`` syscall; on Linux pid 0 means
  the calling thread, so serving workers pin independently).  Moved here
  from ``benchmarks/harness.py`` so benchmarks and serving workers share
  one implementation; the harness keeps a thin re-export.
* :func:`worker_cpu_sets` — partition the allowed CPUs round-robin into
  per-worker affinity sets for ``AsyncServer(workers=n, pin="auto")``.
* :func:`apply_serving_env` — allocator/threading hygiene for serving
  processes: tcmalloc ``LD_PRELOAD`` detection (recommended for the many
  short-lived buffers a serving loop allocates), large-alloc report
  suppression, and log-noise defaults.  Warn-don't-fail when tcmalloc is
  not installed.
"""
from __future__ import annotations

import os
import warnings
from typing import Dict, List, MutableMapping, Optional, Sequence, Tuple

DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# env defaults applied (setdefault, never overriding the user) by
# apply_serving_env; see SNIPPETS.md 3 for the provenance of each
SERVING_ENV_PRESET: Dict[str, str] = {
    # tcmalloc reports every allocation over ~1GB by default; padded
    # NCHW buffers at large buckets trip it constantly
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    # silence TF/XLA C++ INFO+WARNING chatter in serving logs
    "TF_CPP_MIN_LOG_LEVEL": "2",
}

TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
)


# ---------------------------------------------------------------------------
# XLA_FLAGS merging
# ---------------------------------------------------------------------------

def merge_xla_flag(flags: str, flag: str, value) -> str:
    """Return ``flags`` with ``flag=value`` set, replacing any existing
    assignment of the same flag and preserving every other token."""
    kept = [t for t in flags.split()
            if t != flag and not t.startswith(flag + "=")]
    kept.append(f"{flag}={value}")
    return " ".join(kept)


def parse_xla_flag(flags: str, flag: str) -> Optional[str]:
    """The value of ``flag`` in an ``XLA_FLAGS`` string, or None."""
    for t in flags.split():
        if t.startswith(flag + "="):
            return t.split("=", 1)[1]
    return None


def configure_cpu_devices(n: int, *,
                          env: MutableMapping[str, str] = os.environ,
                          warn_oversubscribe: bool = True) -> int:
    """Expose ``n`` host cores as JAX CPU devices for this process.

    Merges ``--xla_force_host_platform_device_count=n`` into
    ``env["XLA_FLAGS"]`` — existing user flags are preserved, an existing
    device-count assignment is replaced (never duplicated).  Must run
    before the first JAX backend use: after it, the flag affects only
    child processes.  ``n`` larger than the host's core count is allowed —
    placeholder-device dry-runs depend on it — but warns unless
    ``warn_oversubscribe=False``.  Returns ``n``.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    total = os.cpu_count() or 1
    if warn_oversubscribe and n > total:
        warnings.warn(
            f"requesting {n} CPU devices on a {total}-core host: devices "
            "beyond the core count time-share and will not scale "
            "(expected only for placeholder-device dry-runs)",
            RuntimeWarning, stacklevel=2)
    env["XLA_FLAGS"] = merge_xla_flag(env.get("XLA_FLAGS", ""),
                                      DEVICE_COUNT_FLAG, n)
    return n


def cpu_platform_allowed(env: MutableMapping[str, str] = os.environ
                         ) -> bool:
    """Whether JAX may run this process on its CPU backend:
    ``JAX_PLATFORMS`` unset, or naming no platform but cpu."""
    return set(env.get("JAX_PLATFORMS", "").split(",")) <= {"", "cpu"}


def configured_device_count(env: MutableMapping[str, str] = os.environ
                            ) -> Optional[int]:
    """The device count currently forced in ``env``, or None."""
    v = parse_xla_flag(env.get("XLA_FLAGS", ""), DEVICE_COUNT_FLAG)
    return int(v) if v is not None else None


# ---------------------------------------------------------------------------
# CPU pinning (threads and processes)
# ---------------------------------------------------------------------------

_pin_done = False


def maybe_pin(cpus: Optional[Sequence[int]] = None
              ) -> Optional[Tuple[int, ...]]:
    """Pin the calling thread to ``cpus`` when pinning is requested and
    available.  With explicit ``cpus`` pinning is always attempted; with
    ``cpus=None`` it is opt-in via ``BENCH_PIN=1`` (pins to the lowest
    allowed core — the benchmark-harness behavior).  Silently a no-op
    where the platform lacks ``sched_setaffinity`` (the same syscall
    ``taskset`` uses) or the container forbids it.  On Linux the affinity
    call targets the calling *thread*, so each serving worker pins itself
    independently.  Returns the pinned set, or None."""
    global _pin_done
    if cpus is None:
        if os.environ.get("BENCH_PIN", "") not in ("1", "true"):
            return None
        if not hasattr(os, "sched_getaffinity"):
            return None
        cpus = [min(os.sched_getaffinity(0))]
    if not hasattr(os, "sched_setaffinity"):
        return None
    try:
        os.sched_setaffinity(0, set(cpus))
    except (OSError, ValueError):
        return None
    if not _pin_done:
        print(f"# pinned to CPU(s) {sorted(cpus)}", flush=True)
        _pin_done = True
    return tuple(sorted(cpus))


def worker_cpu_sets(n_workers: int,
                    cpus: Optional[Sequence[int]] = None
                    ) -> List[Tuple[int, ...]]:
    """Partition the allowed CPUs into ``n_workers`` disjoint affinity
    sets, round-robin so every worker gets a share even when the counts
    do not divide.  With fewer cores than workers, sets repeat (two
    workers may share a core — still better than the scheduler migrating
    both).  Used by ``AsyncServer(pin="auto")``."""
    if n_workers < 1:
        raise ValueError(f"n_workers must be >= 1, got {n_workers}")
    if cpus is None:
        if hasattr(os, "sched_getaffinity"):
            cpus = sorted(os.sched_getaffinity(0))
        else:
            cpus = list(range(os.cpu_count() or 1))
    cpus = list(cpus)
    if len(cpus) >= n_workers:
        return [tuple(cpus[i::n_workers]) for i in range(n_workers)]
    return [(cpus[i % len(cpus)],) for i in range(n_workers)]


# ---------------------------------------------------------------------------
# Allocator / env hygiene for serving processes
# ---------------------------------------------------------------------------

def find_tcmalloc() -> Optional[str]:
    """Path to an installed tcmalloc shared library, or None."""
    for p in TCMALLOC_CANDIDATES:
        if os.path.exists(p):
            return p
    try:
        import ctypes.util
        name = ctypes.util.find_library("tcmalloc") \
            or ctypes.util.find_library("tcmalloc_minimal")
        return name
    except Exception:       # noqa: BLE001 — detection is best-effort
        return None


def tcmalloc_active() -> bool:
    """Is tcmalloc already loaded into this process (LD_PRELOAD took
    effect before we started)?"""
    try:
        with open("/proc/self/maps") as f:
            return "tcmalloc" in f.read()
    except OSError:
        return "tcmalloc" in os.environ.get("LD_PRELOAD", "")


def apply_serving_env(env: MutableMapping[str, str] = os.environ, *,
                      quiet: bool = False) -> Dict[str, str]:
    """Apply the recommended serving-process environment (warn-don't-fail).

    * ``SERVING_ENV_PRESET`` keys are set only where unset (never
      overrides the user);
    * tcmalloc: if already active, nothing to do; if installed but not
      preloaded, ``LD_PRELOAD`` is exported so *child* processes get it
      and a warning explains the current process keeps the default
      allocator (LD_PRELOAD cannot be applied retroactively); if absent,
      a warning recommends installing it.

    Returns the settings this call added to ``env``.
    """
    applied: Dict[str, str] = {}
    for k, v in SERVING_ENV_PRESET.items():
        if k not in env:
            env[k] = v
            applied[k] = v
    if not tcmalloc_active():
        lib = find_tcmalloc()
        if lib is None:
            if not quiet:
                warnings.warn(
                    "tcmalloc not found: serving keeps the default "
                    "allocator (install libtcmalloc and LD_PRELOAD it "
                    "for faster malloc under concurrent workers)",
                    RuntimeWarning, stacklevel=2)
        else:
            preload = env.get("LD_PRELOAD", "")
            if lib not in preload.split(os.pathsep if ":" in preload
                                        else " ") and lib not in preload:
                env["LD_PRELOAD"] = f"{preload}:{lib}".lstrip(":")
                applied["LD_PRELOAD"] = env["LD_PRELOAD"]
            if not quiet:
                warnings.warn(
                    f"tcmalloc found at {lib} but not preloaded; exported "
                    "LD_PRELOAD for child processes — relaunch under it "
                    "(LD_PRELOAD=" + lib + " python -m repro.launch.serve "
                    "...) to use it in this process",
                    RuntimeWarning, stacklevel=2)
    return applied
