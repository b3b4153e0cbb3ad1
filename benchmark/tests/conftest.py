"""The benchmark's tests rehearse on the CPU backend with 8 simulated
devices (a four-chip cell needs four), set before JAX initialises.

A rehearsal shrinks only the configuration's fact table
(``--rehearse-rows``).  A four-chip configuration's dimension table is
20M rows too, and the CPU's simulated mesh takes longer than a test may
over a sample-sort of that size; so, as ``small_mirror_cap`` does for the
serve cell, the rehearsal cuts that table and lowers, in this process
only, the sizes at which the program takes the mesh sort and the
partitioned probe.  No run on the chip passes through here.
"""

import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

REHEARSAL_DIMENSION_ROWS = 100_000


@pytest.fixture(autouse=True)
def small_mesh_deployment(monkeypatch):
    import run
    from csvplus_tpu.ops import sort
    from csvplus_tpu.ops.join import DeviceIndex

    real = run.load_json

    def load_json(*parts):
        loaded = real(*parts)
        if parts[0] == "configs" and int(loaded.get("chips", 1)) > 1:
            for name, table in loaded["tables"].items():
                if name != loaded["fact"]:
                    table["rows"] = min(int(table["rows"]), REHEARSAL_DIMENSION_ROWS)
        return loaded

    monkeypatch.setattr(run, "load_json", load_json)
    monkeypatch.setattr(DeviceIndex, "PARTITION_MIN_KEYS", 1000)
    monkeypatch.setattr(sort, "DSORT_MIN_ROWS", 1000)
