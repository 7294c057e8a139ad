"""Launchers: production mesh, step builders, dry-run driver, train/serve
entry points, platform/backend selection."""

from repro.launch.platform import (GPU_XLA_FLAGS, platform_diagnostics,
                                   set_host_cpu_devices, set_platform,
                                   use_compile_cache)

__all__ = ["GPU_XLA_FLAGS", "platform_diagnostics",
           "set_host_cpu_devices", "set_platform", "use_compile_cache"]
