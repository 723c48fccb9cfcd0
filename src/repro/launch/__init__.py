"""Launchers: the compile-cache location, CPU runtime config (host
devices, pinning, env hygiene), mesh construction, multi-pod dry-run,
train/serve drivers."""
from repro.launch.cache import enable_compile_cache
from repro.launch.cpu import (apply_serving_env, configure_cpu_devices,
                              configured_device_count, maybe_pin,
                              worker_cpu_sets)

__all__ = ["apply_serving_env", "configure_cpu_devices",
           "configured_device_count", "enable_compile_cache", "maybe_pin",
           "worker_cpu_sets"]
