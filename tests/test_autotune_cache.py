"""Autotuner tile-cache contract (ISSUE 2 satellite): corrupt or stale
cache entries degrade to the heuristic tile (never crash a dispatch),
cache hits skip the sweep, the env override wins, and every dispatch
decision resolves BEFORE trace time."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from byzpy_tpu.ops import pallas_kernels as pk
from byzpy_tpu.ops import robust
from byzpy_tpu.profiling import autotune, tilecache


@pytest.fixture
def cache_file(tmp_path, monkeypatch):
    path = str(tmp_path / "tiles.json")
    monkeypatch.setenv("BYZPY_TPU_TUNE_CACHE", path)
    return path


def test_cache_round_trip(cache_file):
    tilecache.store("selection", platform="cpu", n=64, d=65536, tile=4096,
                    ms=1.25)
    assert tilecache.lookup("selection", platform="cpu", n=64, d=65536) == 4096
    # persisted on disk, reloadable from a fresh read
    data = json.load(open(cache_file))
    assert data["selection:cpu:64x65536"]["tile"] == 4096
    assert data["selection:cpu:64x65536"]["ms"] == 1.25
    # distinct keys don't collide
    assert tilecache.lookup("selection", platform="cpu", n=64, d=1024) is None
    assert tilecache.lookup("meamed", platform="cpu", n=64, d=65536) is None


def test_corrupt_cache_degrades_to_heuristic(cache_file):
    with open(cache_file, "w") as fh:
        fh.write("{not json at all")
    assert tilecache.lookup("selection", platform="cpu", n=64, d=65536) is None
    assert tilecache.load_cache() == {}
    # dispatch still works end to end on a corrupt cache
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 256), jnp.float32)
    out = pk.selection_mean_stream_pallas(x[None], f=1, q=3, mode="krum")[0]
    assert out.shape == (256,)
    # and store() recovers the file
    tilecache.store("gram", platform="cpu", n=8, d=256, tile=128)
    assert tilecache.lookup("gram", platform="cpu", n=8, d=256) == 128


@pytest.mark.parametrize(
    "bad", [0, -128, 100, 1 << 20, "4096", 4096.0, None, True]
)
def test_stale_entry_values_are_ignored(cache_file, bad):
    with open(cache_file, "w") as fh:
        json.dump({"selection:cpu:64x65536": {"tile": bad}}, fh)
    assert tilecache.lookup("selection", platform="cpu", n=64, d=65536) is None
    assert not tilecache.valid_tile(bad)


def test_cache_hit_skips_sweep(cache_file, monkeypatch):
    tilecache.store("gram", platform=jax.default_backend(), n=8, d=256,
                    tile=256)
    ran = []
    monkeypatch.setattr(
        autotune, "_kernel_runner",
        lambda family: ran.append(family) or (lambda x, t: x),
    )
    row = autotune.sweep("gram", n=8, d=256)
    assert row["cached"] is True and row["tile"] == 256
    assert ran == []  # no kernel was ever invoked
    # force=True re-measures
    row = autotune.sweep("gram", n=8, d=256, force=True, repeat=1,
                         candidates=[128], verbose=False)
    assert row["cached"] is False


def test_env_override_beats_cache(cache_file, monkeypatch):
    tilecache.store("selection", platform=jax.default_backend(), n=8, d=512,
                    tile=512)
    assert pk._tuned_tile("selection", 8, 512) == 512
    monkeypatch.setenv("BYZPY_TPU_TILE_SELECTION", "256")
    assert pk._tuned_tile("selection", 8, 512) == 256
    # malformed env values fall through to the cache
    monkeypatch.setenv("BYZPY_TPU_TILE_SELECTION", "not-a-tile")
    assert pk._tuned_tile("selection", 8, 512) == 512
    monkeypatch.setenv("BYZPY_TPU_TILE_SELECTION", "100")  # not lane-aligned
    assert pk._tuned_tile("selection", 8, 512) == 512


def test_sweep_persists_winner(cache_file):
    row = autotune.sweep(
        "gram", n=8, d=256, candidates=[128, 256], repeat=1, verbose=False
    )
    assert row["cached"] is False
    assert row["tile"] in (128, 256)
    hit = tilecache.lookup(
        "gram", platform=jax.default_backend(), n=8, d=256
    )
    assert hit == row["tile"]
    entry = tilecache.load_cache()[
        tilecache.cache_key("gram", platform=jax.default_backend(), n=8, d=256)
    ]
    assert set(entry["candidates"]) == {"128", "256"}


def test_dispatch_decisions_resolve_before_trace(cache_file, monkeypatch):
    """The stale-closure pitfall: env-var dispatch knobs used to be read
    inside jitted functions, so flipping them after a shape had traced
    changed nothing. All knobs now resolve in the Python wrappers —
    flipping one between two calls of the SAME shape changes the very
    next dispatch."""
    calls = []
    real = pk.meamed_stream_pallas

    def spy(xs, **kw):
        calls.append(xs.shape)
        # the dispatch pretended to be on chip (below); the kernel itself
        # runs where it really is — interpreted, which a TPU forbids
        with monkeypatch.context() as really:
            really.setattr(
                "byzpy_tpu.ops.pallas_kernels._on_tpu", lambda: False
            )
            return real(xs, **kw)

    monkeypatch.setattr(
        "byzpy_tpu.ops.pallas_kernels.meamed_stream_pallas", spy
    )
    # pretend we're on chip so the floor (not the platform) is the gate:
    # the forced BYZPY_TPU_PALLAS=1 flag bypasses min_dim by design
    monkeypatch.setattr("byzpy_tpu.ops.pallas_kernels._on_tpu", lambda: True)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 512), jnp.float32)

    # floor above d: XLA path, kernel untouched
    monkeypatch.setenv("BYZPY_TPU_MEAMED_MIN_DIM", "100000")
    a = robust.mean_of_medians(x, f=2)
    assert calls == []
    # SAME shape, floor flipped below d: the kernel dispatches immediately
    monkeypatch.setenv("BYZPY_TPU_MEAMED_MIN_DIM", "128")
    b = robust.mean_of_medians(x, f=2)
    assert calls == [(1, 9, 512)]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)


def test_tile_override_resolves_before_trace(cache_file, monkeypatch):
    """Same-shape calls honor a BYZPY_TPU_TILE_* flip (tile is a static
    argument of the inner jit, so a new value retraces rather than
    reusing the stale closure)."""
    seen = []
    real = pk._sorted_reduce_stream_call

    def spy(xs, **kw):
        seen.append(kw["tile"])
        return real(xs, **kw)

    monkeypatch.setattr(
        "byzpy_tpu.ops.pallas_kernels._sorted_reduce_stream_call", spy
    )
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 512), jnp.float32)
    pk.sorted_reduce_stream_pallas(x[None], mode="median")
    monkeypatch.setenv("BYZPY_TPU_TILE_SORTED_REDUCE", "128")
    pk.sorted_reduce_stream_pallas(x[None], mode="median")
    assert len(seen) == 2 and seen[1] == 128 and seen[0] != 128


def test_invalid_store_rejected(cache_file):
    with pytest.raises(ValueError):
        tilecache.store("gram", platform="cpu", n=8, d=256, tile=100)
