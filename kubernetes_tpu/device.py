"""Which device a run is on, and where its compiled programs are kept.

`chip_smoke.py` and `bench.py` call `use_compile_cache()` first and then
`require_tpu()`. Importing this module touches no device: the chip belongs to
one process at a time, and `spawn` workers re-import their parent's modules.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

# <checkout>/.jax_cache (gitignored). A fixed path: the cache keys on it, so a
# directory that moves between runs never hits.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


class NoTPUError(RuntimeError):
    """The default backend is not a TPU and no CPU rehearsal was named."""


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at CACHE_DIR, unless
    JAX_COMPILATION_CACHE_DIR already places it (then nothing is set and JAX
    reads that variable itself). Call before the first compile. Returns the
    directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def cpu_rehearsal() -> bool:
    """True when the caller named a CPU rehearsal with JAX_PLATFORMS=cpu."""
    return os.environ.get("JAX_PLATFORMS", "") == "cpu"


def require_tpu(timeout_s: float = 120.0) -> Dict:
    """Bring the default backend up and run one op on it, in a thread bounded
    by `timeout_s` (a wedged backend fails here instead of at the caller's
    outer deadline). Returns {"platform", "kind", "count"} as JAX reports them.

    Raises NoTPUError when the backend is not a TPU, unless the run is a named
    CPU rehearsal; raises RuntimeError when the backend fails or hangs. There
    is no fallback: a run that finds no chip produces no chip numbers."""
    out: Dict = {}

    def probe():
        try:
            import jax
            import jax.numpy as jnp

            devs = jax.devices()
            (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
            out.update(platform=devs[0].platform, kind=devs[0].device_kind,
                       count=len(devs))
        except Exception as e:  # reported to the caller below
            out["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout=timeout_s)
    if t.is_alive():
        raise RuntimeError(f"device backend unresponsive after {timeout_s:.0f}s")
    if "error" in out:
        raise RuntimeError(f"device backend failed: {out['error']}")
    if out["platform"] != "tpu" and not cpu_rehearsal():
        raise NoTPUError(
            f"no TPU: JAX found platform {out['platform']!r} "
            f"({out['kind']}, {out['count']} device(s)); set JAX_PLATFORMS=cpu "
            f"to run a CPU rehearsal")
    return out
