"""repro.kernels.tune: cache roundtrip, resolution precedence, clamps.

The acceptance contract of the autotune layer: a cache miss is bitwise
the pre-autotune behaviour (``DEFAULT_BLOCKS``), explicit overrides beat
the active cache which beats the default, the interpret-mode sweep is
deterministic (same shapes -> byte-identical cache JSON), and block
clamping warns once and is recorded on the live cache entry.
"""

import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nladc import build_ramp
from repro.kernels import ops, tune


@pytest.fixture(autouse=True)
def _clean_tune_state(monkeypatch):
    """Every test starts from pristine module state + no tune env vars."""
    for var in ("REPRO_KERNEL_CACHE", "REPRO_KERNEL_BLOCKS"):
        monkeypatch.delenv(var, raising=False)
    tune._reset_for_tests()
    yield
    tune._reset_for_tests()


SHAPE_MM = (64, 96, 160)          # (m, k, n) for fused_matmul_nladc
SHAPE_EW = (48, 80)               # (m, n) for nladc


def _mini_cache(blocks_mm=(32, 32, 32), blocks_ew=(16, 16)):
    cache = tune.TuneCache(meta={"note": "test"})
    cache.record("fused_matmul_nladc", SHAPE_MM, jnp.float32, blocks_mm,
                 source="proxy")
    cache.record("nladc", SHAPE_EW, jnp.float32, blocks_ew, source="proxy")
    return cache


# ---------------------------------------------------------------------------
# Serialization
# ---------------------------------------------------------------------------

def test_cache_json_roundtrip(tmp_path):
    cache = _mini_cache()
    path = str(tmp_path / "tune.json")
    cache.save(path)
    loaded = tune.TuneCache.load(path)
    assert loaded.to_dict() == cache.to_dict()
    assert loaded.lookup("fused_matmul_nladc", SHAPE_MM) == (32, 32, 32)
    assert loaded.lookup("nladc", SHAPE_EW) == (16, 16)
    # a different shape is a miss, not an error
    assert loaded.lookup("nladc", (7, 7)) is None


def test_cache_load_accepts_bench_wrapper(tmp_path):
    """--kernel-cache benchmarks/BENCH_kernels.json works directly: the
    loader unwraps the benchmark output's 'tune' section."""
    cache = _mini_cache()
    path = str(tmp_path / "BENCH_kernels.json")
    with open(path, "w") as f:
        json.dump({"quick": True, "tune": cache.to_dict()}, f)
    loaded = tune.TuneCache.load(path)
    assert loaded.lookup("nladc", SHAPE_EW) == (16, 16)


def test_cache_rejects_garbage(tmp_path):
    with pytest.raises(ValueError, match="entries"):
        tune.TuneCache.from_dict({"not": "a cache"})
    with pytest.raises(ValueError, match="version"):
        tune.TuneCache.from_dict({"entries": {}, "version": 99})


# ---------------------------------------------------------------------------
# Resolution precedence: override > cache > default
# ---------------------------------------------------------------------------

def test_cache_miss_falls_back_to_default_blocks():
    """No cache, no overrides -> the kernel module's historical constant
    (the bitwise-no-change guarantee)."""
    import importlib

    fm = importlib.import_module("repro.kernels.fused_matmul_nladc")
    nk = importlib.import_module("repro.kernels.nladc_kernel")
    assert tune.resolve_blocks("fused_matmul_nladc", SHAPE_MM) \
        == tuple(fm.DEFAULT_BLOCKS)
    assert tune.resolve_blocks("nladc", SHAPE_EW) == tuple(nk.DEFAULT_BLOCK)
    # an active cache that misses this shape also falls through
    tune.set_active_cache(tune.TuneCache())
    assert tune.resolve_blocks("nladc", SHAPE_EW) == tuple(nk.DEFAULT_BLOCK)


def test_active_cache_hit_wins_over_default():
    tune.set_active_cache(_mini_cache())
    assert tune.resolve_blocks("fused_matmul_nladc", SHAPE_MM) == (32, 32, 32)
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (16, 16)


def test_override_wins_over_cache(monkeypatch):
    tune.set_active_cache(_mini_cache())
    tune.set_block_overrides("nladc=64x64")
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (64, 64)
    # the other kernel still resolves from the cache
    assert tune.resolve_blocks("fused_matmul_nladc", SHAPE_MM) == (32, 32, 32)
    tune.clear_block_overrides()
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (16, 16)
    # env-var override has the same precedence as the CLI one
    monkeypatch.setenv("REPRO_KERNEL_BLOCKS", "nladc=128x32")
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (128, 32)


def test_env_cache_loaded_lazily(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    _mini_cache().save(path)
    monkeypatch.setenv("REPRO_KERNEL_CACHE", path)
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (16, 16)
    # an explicitly installed cache wins over the env path
    tune.set_active_cache(_mini_cache(blocks_ew=(48, 80)))
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (48, 80)


def test_configure_cli_hookup(tmp_path):
    path = str(tmp_path / "tune.json")
    _mini_cache().save(path)
    tune.configure("fused_matmul_nladc=64x32x96", path)
    assert tune.resolve_blocks("fused_matmul_nladc", SHAPE_MM) == (64, 32, 96)
    assert tune.resolve_blocks("nladc", SHAPE_EW) == (16, 16)


def test_parse_block_spec_errors():
    with pytest.raises(ValueError, match="unknown tunable kernel"):
        tune.parse_block_spec("bogus=1x2")
    with pytest.raises(ValueError, match="KERNEL=BMxBNxBK"):
        tune.parse_block_spec("nladc")
    with pytest.raises(ValueError, match="block extents"):
        tune.parse_block_spec("nladc=128")          # wrong rank
    with pytest.raises(ValueError, match="block extents"):
        tune.parse_block_spec("nladc=128x-4")       # non-positive
    # multiple kernels in one spec
    out = tune.parse_block_spec(
        "fused_matmul_nladc=128x128x512, nladc=256x512")
    assert out == {"fused_matmul_nladc": (128, 128, 512),
                   "nladc": (256, 512)}


# ---------------------------------------------------------------------------
# The wrappers actually consult the resolver (bitwise-invariant numerics)
# ---------------------------------------------------------------------------

def test_ops_resolve_from_cache_bitwise_invariant(rng):
    """Blocks from a cache hit change tiling only: output stays bitwise
    equal to the default-blocks call."""
    ramp = build_ramp("swish", 5)
    m, k, n = SHAPE_MM
    x = jnp.asarray(rng.normal(0, 0.4, (m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (k, n)).astype(np.float32))
    y_default = np.asarray(ops.fused_matmul_nladc(x, w, ramp))
    tune.set_active_cache(_mini_cache())
    y_cached = np.asarray(ops.fused_matmul_nladc(x, w, ramp))
    np.testing.assert_array_equal(y_default, y_cached)

    xe = jnp.asarray(rng.normal(0, 2, SHAPE_EW).astype(np.float32))
    tune.set_active_cache(None)
    y_d = np.asarray(ops.nladc(xe, ramp))
    tune.set_active_cache(_mini_cache())
    np.testing.assert_array_equal(y_d, np.asarray(ops.nladc(xe, ramp)))


# ---------------------------------------------------------------------------
# Clamp accounting
# ---------------------------------------------------------------------------

def test_clamp_warns_once_and_records(rng):
    """An oversized requested block warns exactly once per kernel x shape
    x request and lands in the active cache's entry.

    The clamp seam is the pallas-level function (the ``ops`` wrappers pad
    the operand up to the block instead of clamping)."""
    from repro.kernels import nladc_kernel as nk

    ramp = build_ramp("sigmoid", 5)
    cache = tune.TuneCache()
    tune.set_active_cache(cache)
    x = jnp.asarray(rng.normal(0, 2, (8, 24)).astype(np.float32))

    with pytest.warns(tune.KernelBlockClampWarning, match="clamped"):
        y1 = nk.nladc_pallas(x, ramp, block=(512, 512), interpret=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", tune.KernelBlockClampWarning)
        y2 = nk.nladc_pallas(x, ramp, block=(512, 512),
                             interpret=True)     # same request: silent
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    key = tune.cache_key("nladc", (8, 24))
    entry = cache.entries[key]
    assert entry["clamped"]["requested"] == [512, 512]
    assert entry["clamped"]["applied"] == [8, 24]
    assert tuple(entry["blocks"]) == (8, 24)


# ---------------------------------------------------------------------------
# The sweep (interpret-mode proxy scoring: deterministic)
# ---------------------------------------------------------------------------

def test_autotune_sweep_deterministic(tmp_path):
    shapes = {"fused_matmul_nladc": [SHAPE_MM], "nladc": [SHAPE_EW]}
    a = tune.autotune(shapes, measure="proxy")
    b = tune.autotune(shapes, measure="proxy")
    assert a.to_dict()["entries"] == b.to_dict()["entries"]
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    a.save(pa)
    b.save(pb)
    ja = open(pa).read()
    assert "entries" in ja and ja == open(pb).read()

    # every swept cell resolves and carries proxy metadata
    for kernel, shape in (("fused_matmul_nladc", SHAPE_MM),
                          ("nladc", SHAPE_EW)):
        entry = a.entries[tune.cache_key(kernel, shape)]
        assert entry["source"] == "proxy"
        assert entry["score"] > 0
        blocks = a.lookup(kernel, shape)
        dims = tune._BLOCK_DIMS[kernel]
        for blk, d in zip(blocks, dims):
            assert 0 < blk <= shape[d]


def test_autotune_records_clamped_candidates():
    """Shapes smaller than every candidate tile win via clamping and the
    cache entry says so."""
    cache = tune.autotune({"nladc": [(8, 24)]}, measure="proxy")
    entry = cache.entries[tune.cache_key("nladc", (8, 24))]
    assert tuple(entry["blocks"]) == (8, 24)
    assert entry["clamped"]["applied"] == [8, 24]
