"""Memory watermark sampling + host facts for bench artifacts.

The r06 mesh-RSS regression (7.2 -> 11.8GB under whole-program fusion)
was only caught because one bench script happened to probe
``ru_maxrss``.  This module makes that probe a subsystem:

* :func:`rss_mb` — CURRENT resident set (``/proc/self/statm``), what
  the metrics pump samples;
* :func:`peak_rss_mb` — process-lifetime high watermark (``VmHWM``,
  falling back to ``ru_maxrss``), the number the artifacts record;
* :func:`device_memory_stats` — per-device ``bytes_in_use`` /
  ``peak_bytes_in_use`` from jax where the backend reports them (CPU
  returns nothing; the call degrades to ``{}``);
* :func:`host_header` — the (host_cpus, device_count, platform,
  device_kind) facts every bench artifact must carry (the r07/r08
  postmortems both needed them and only some artifacts had them).
"""

from __future__ import annotations

import os
from typing import Any, Dict

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def rss_mb() -> float:
    """Current resident set size in MB (0.0 when unreadable)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * _PAGE_SIZE / 1e6
    except (OSError, ValueError, IndexError):
        return 0.0


def peak_rss_mb() -> float:
    """Process-lifetime peak RSS in MB: ``VmHWM`` when procfs is
    available, else ``ru_maxrss`` (which Linux reports in KB)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1e3
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e3
    except Exception:
        return 0.0


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """``{device: {bytes_in_use, peak_bytes_in_use, ...}}`` for devices
    whose backend exposes ``memory_stats()`` (TPU/GPU); ``{}`` on CPU
    and on any failure — callers must treat device stats as optional."""
    out: Dict[str, Dict[str, int]] = {}
    try:
        import jax

        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats:
                out[str(d)] = {
                    k: int(v)
                    for k, v in stats.items()
                    if isinstance(v, (int, float))
                }
    except Exception:
        return {}
    return out


def host_header() -> Dict[str, Any]:
    """The artifact header facts every bench record must carry: the
    device as jax reports it, and the host's core count."""
    import jax

    dev = jax.devices()[0]
    return {
        "host_cpus": os.cpu_count() or 1,
        "jax_device_count": jax.device_count(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }
