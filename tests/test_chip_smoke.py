"""CPU rehearsal of chip_smoke.py: its legs at a tiny size, and the
branches of its exit code a machine without a chip can reach."""

import io
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_wrong_platform_exits_nonzero_and_prints_no_result(capsys):
    out = io.StringIO()
    assert chip_smoke.main(["--rows", "1000"], out=out) != 0
    assert out.getvalue() == ""  # no result line, nothing was run
    assert "not a TPU" in capsys.readouterr().err


def test_rehearse_every_leg_on_cpu(monkeypatch):
    """All six legs at 20K rows on the 8 simulated devices.  The sizes
    that select the streamed tier, the sample-sort and the all_to_all
    probe are lowered here — chip_smoke.py itself overrides none."""
    import csvplus_tpu.ops.join as J
    import csvplus_tpu.ops.sort as S

    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "65536")
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    monkeypatch.setattr(S, "DSORT_MIN_ROWS", 1)
    monkeypatch.setattr(chip_smoke, "N_PROD", 20)  # the filter keeps ~10 rows
    out = io.StringIO()
    assert chip_smoke.main(["--rows", "20000", "--allow-cpu"], out=out) == 0
    lines = out.getvalue().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    text = "\n".join(lines)
    assert "orders=ingest:streamed" in text and "ingest:python" not in text
    assert "leg2 join warm shards=1" in text and "lowered=0" in text
    assert "FusedProbe" in text and "mask kernel interpreted" in text
    assert "filter chain: 0 rows" not in text
    assert "degraded=0" in text and "after recovery" in text
    for sharded in ("leg1 ingest shards=4", "leg2 join warm shards=4"):
        assert sharded in text
    assert "leg4 UniqueIndexOn(order_id) shards=4" in text and "dsort=True" in text
    assert "leg5 returns.Join(order_idx) shards=4" in text
    assert "all_to_all=True" in text


def test_a_differing_comparison_fails_the_run(monkeypatch):
    real_init = chip_smoke.Data.__init__

    def corrupt(self, *a, **kw):
        real_init(self, *a, **kw)
        self.qty[7] += 1  # the reference no longer matches the file

    monkeypatch.setattr(chip_smoke.Data, "__init__", corrupt)
    with pytest.raises(chip_smoke.SmokeFailure, match="qty"):
        chip_smoke.main(["--rows", "2000", "--allow-cpu"], out=io.StringIO())


def test_an_unbuildable_scanner_fails_the_run(monkeypatch, tmp_path):
    from csvplus_tpu.native import scanner

    monkeypatch.setattr(scanner, "_SRC", str(tmp_path / "missing.cpp"))
    monkeypatch.setattr(scanner, "_SO", str(tmp_path / "_scanner.so"))
    with pytest.raises(ImportError, match="native scanner build failed"):
        chip_smoke.main(["--rows", "2000", "--allow-cpu"], out=io.StringIO())


def test_compile_cache_placement(monkeypatch, tmp_path):
    """An externally placed cache is left alone; otherwise a non-CPU
    backend gets the one fixed path inside the checkout."""
    import jax

    from csvplus_tpu.utils import compile_cache

    before = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    try:
        # placed from outside (JAX_COMPILATION_CACHE_DIR lands in this
        # config value at import): nothing is touched
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert compile_cache.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == before[1]
        # not placed, CPU backend: no cache of its own
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir is None
        # not placed, a chip: the fixed git-ignored path in the checkout
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert compile_cache.place_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
