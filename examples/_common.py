"""Shared example bootstrap: repo-root import path, virtual mesh, compile cache."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bootstrap(virtual_devices: int = 0) -> None:
    """Call before the first JAX use. The examples run on whatever backend
    JAX selects; ``JAX_PLATFORMS=cpu`` is the one way to pick the CPU, and
    ``virtual_devices`` then gives it an N-device virtual mesh (the XLA flag
    only affects the host platform). Places the persistent compile cache
    (``windflow_tpu/runtime/compile_cache.py``)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if virtual_devices and "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={virtual_devices}"
        ).strip()
    from windflow_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
