"""repro.kernels.tune: config-cache round-trip, sweep memoization,
roofline pruning, and the telemetry export the capacity planner ingests.

The sweeps here use the "smoke" preset shapes (interpret-mode / CPU-proxy
timings) so the whole module runs in tier-1; the full-preset sweep runs
in the non-blocking slow CI job via the module CLI."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tune import (
    FAMILIES,
    SWEEP_SHAPES,
    ConfigCache,
    bench_rows,
    cache_key,
    candidates_for,
    decode_step_rows,
    ensure,
    sweep,
)
from repro.kernels.tune.roofline import (
    VMEM_BUDGET,
    estimate,
    light_speed_s,
    prune,
)
from repro.serve import CapacityPlanner

SHAPE = dict(SWEEP_SHAPES["smoke"]["flash_decode_paged"])


# ------------------------------------------------------------------- cache
def test_config_cache_roundtrip(tmp_path):
    path = tmp_path / "tune.json"
    cache = ConfigCache(str(path))
    key = cache_key("flash_decode_paged", SHAPE, jnp.float32, backend="cpu")
    assert "flash_decode_paged|" in key and "|float32|cpu" in key
    cache.put(key, family="flash_decode_paged", shape=SHAPE,
              dtype=jnp.float32, config={"pages_per_program": 2},
              us_per_call=123.4, swept=3, pruned=4, backend="cpu")
    cache.save()
    # a fresh instance reads the same entry back
    reloaded = ConfigCache(str(path))
    entry = reloaded.get(key)
    assert entry["config"] == {"pages_per_program": 2}
    assert entry["us_per_call"] == pytest.approx(123.4)
    assert entry["candidates_swept"] == 3 and entry["candidates_pruned"] == 4
    assert reloaded.config(key) == {"pages_per_program": 2}
    # the file is plain JSON with a schema version
    payload = json.loads(path.read_text())
    assert payload["version"] == 1 and key in payload["entries"]
    # a stale schema version is discarded, not misread
    payload["version"] = 0
    path.write_text(json.dumps(payload))
    assert ConfigCache(str(path)).entries == {}


def test_cache_key_dtype_and_backend_separation():
    k1 = cache_key("ssm_scan", {"s": 64}, jnp.float32, backend="cpu")
    k2 = cache_key("ssm_scan", {"s": 64}, jnp.bfloat16, backend="cpu")
    k3 = cache_key("ssm_scan", {"s": 64}, jnp.float32, backend="tpu")
    assert len({k1, k2, k3}) == 3


# ------------------------------------------------------------------- sweep
def test_ensure_returns_cached_config_without_resweeping(tmp_path):
    """Acceptance: the second call for the same (shape, dtype, backend) key
    returns the cached config without re-sweeping."""
    cache = ConfigCache(str(tmp_path / "tune.json"))
    cfg1 = ensure("flash_decode_paged", SHAPE, jnp.float32, cache=cache,
                  iters=1)
    assert cache.sweeps == 1
    cfg2 = ensure("flash_decode_paged", SHAPE, jnp.float32, cache=cache,
                  iters=1)
    assert cfg2 == cfg1
    assert cache.sweeps == 1, "second ensure() must not re-sweep"
    # round-trip through disk: a fresh cache needs no sweep either
    fresh = ConfigCache(str(tmp_path / "tune.json"))
    assert ensure("flash_decode_paged", SHAPE, jnp.float32, cache=fresh,
                  sweep_on_miss=False) == cfg1
    assert fresh.sweeps == 0
    # a different dtype is a different key -> miss without sweep permission
    assert ensure("flash_decode_paged", SHAPE, jnp.bfloat16, cache=fresh,
                  sweep_on_miss=False) is None


@pytest.mark.parametrize("family", FAMILIES)
def test_smoke_sweep_every_family(family):
    """Interpret-mode autotuner smoke: each family sweeps at its smoke
    shape, returns a candidate from its own space, and records pruning."""
    cache = ConfigCache(path=None)  # in-memory
    shape = SWEEP_SHAPES["smoke"][family]
    config, entry = sweep(family, shape, jnp.float32, cache=cache, iters=1)
    assert config in candidates_for(family, shape)
    assert entry["us_per_call"] > 0
    assert entry["candidates_swept"] >= 1
    total = entry["candidates_swept"] + entry["candidates_pruned"]
    assert total == len(candidates_for(family, shape))


# ---------------------------------------------------------------- roofline
def test_roofline_prune_vmem_and_slack():
    shape = {"b": 1, "h": 2, "s": 4096, "d": 128}
    cands = candidates_for("flash_attention", shape)
    kept, n_pruned = prune("flash_attention", shape, cands)
    assert kept, "pruning must keep at least one candidate"
    assert n_pruned + len(kept) == len(cands)
    for est in kept:
        assert est.vmem_bytes <= VMEM_BUDGET
    # modeled times of the kept set stay within the slack of the best
    t_best = min(e.t_model_s for e in kept)
    assert all(e.t_model_s <= 3.0 * t_best + 1e-12 for e in kept)


def test_roofline_estimates_monotone_in_work():
    small = estimate("flash_decode_paged",
                     {"b": 1, "hk": 1, "g": 1, "d": 16, "page": 8,
                      "npp": 4}, {"pages_per_program": 2})
    big = estimate("flash_decode_paged",
                   {"b": 4, "hk": 4, "g": 2, "d": 64, "page": 16,
                    "npp": 128}, {"pages_per_program": 2})
    assert big.flops > small.flops and big.bytes_moved > small.bytes_moved
    assert light_speed_s(big.flops, big.bytes_moved) > light_speed_s(
        small.flops, small.bytes_moved)


@pytest.mark.parametrize("ppp", [4, 8, 16])
def test_roofline_paged_decode_one_program_per_row_group(ppp):
    """The paged-decode model follows the kernel's grid: one program per
    (row, page group) over all KV heads, so VMEM grows with Hk and the
    step count does not."""
    shape = {"b": 16, "hk": 32, "g": 1, "d": 64, "page": 16, "npp": 64}
    est = estimate("flash_decode_paged", shape, {"pages_per_program": ppp})
    assert est.grid_steps == 16 * (64 // ppp)
    assert est.vmem_bytes == (2 * ppp * 32 * 16 * 64 + 32 * ppp * 16
                              + 32 * 66) * 4
    assert est.vmem_bytes <= VMEM_BUDGET
    one_head = estimate("flash_decode_paged", {**shape, "hk": 1},
                        {"pages_per_program": ppp})
    assert one_head.grid_steps == est.grid_steps
    assert est.vmem_bytes > 16 * one_head.vmem_bytes


# --------------------------------------------------------------- telemetry
def _cache_with_decode_entries():
    cache = ConfigCache(path=None)
    for b, us in [(1, 900.0), (2, 1100.0), (4, 1600.0), (8, 2500.0)]:
        shape = {"b": b, "hk": 2, "g": 2, "d": 32, "page": 16, "npp": 32}
        cache.put(cache_key("flash_decode_paged", shape, jnp.float32,
                            backend="cpu"),
                  family="flash_decode_paged", shape=shape,
                  dtype=jnp.float32, config={"pages_per_program": 4},
                  us_per_call=us, swept=2, pruned=5, backend="cpu")
    return cache


def test_bench_rows_shape():
    cache = _cache_with_decode_entries()
    rows = bench_rows(cache)
    assert len(rows) == 4
    name, us, derived = rows[0]
    assert name.startswith("tune/flash_decode_paged/")
    assert us > 0 and "pages_per_program=4" in derived
    assert "swept=2" in derived and "pruned=5" in derived


def test_capacity_planner_fits_on_tuned_kernel_rows():
    """The planner fits its f(b) step model from measured kernel timings
    (scaled to a whole decode step) — measured costs instead of defaults."""
    cache = _cache_with_decode_entries()
    rows = decode_step_rows(cache)
    assert sorted(r["batch"] for r in rows) == [1, 2, 4, 8]
    planner = CapacityPlanner()
    n = planner.observe_tuned_kernels(rows, n_layers=4, overhead_s=1e-4)
    assert n == 4
    planner.fit()
    # step time at batch 4: 4 layers x 1600us + 100us overhead
    assert planner.step_time(4) == pytest.approx(4 * 1.6e-3 + 1e-4, rel=0.2)
    assert planner.step_time(8) > planner.step_time(1)


def test_tuned_lookup_feeds_paged_decode(tmp_path, monkeypatch):
    """The ops wrapper resolves pages_per_program from the default cache
    when not given explicitly (tuned path), falling back to the default
    on a miss."""
    import repro.kernels.tune as tune
    from repro.kernels.flash_decode.ops import (
        DEFAULT_PAGES_PER_PROGRAM,
        _tuned_value,
    )

    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    tune.reset_default_cache()
    try:
        shape = {"b": 2, "hk": 2, "g": 1, "d": 8, "page": 4, "npp": 4}
        # miss -> default
        assert _tuned_value("flash_decode_paged", shape, jnp.float32,
                            "pages_per_program",
                            DEFAULT_PAGES_PER_PROGRAM) == \
            DEFAULT_PAGES_PER_PROGRAM
        cache = ConfigCache(str(path))
        cache.put(cache_key("flash_decode_paged", shape, jnp.float32),
                  family="flash_decode_paged", shape=shape,
                  dtype=jnp.float32, config={"pages_per_program": 2},
                  us_per_call=10.0, swept=1, pruned=0)
        cache.save()
        tune.reset_default_cache()
        assert _tuned_value("flash_decode_paged", shape, jnp.float32,
                            "pages_per_program",
                            DEFAULT_PAGES_PER_PROGRAM) == 2
        # end-to-end: tuned blocking yields the same bits as explicit
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 2, 8), jnp.float32)
        kp = jnp.asarray(rng.randn(9, 2, 4, 8), jnp.float32)
        vp = jnp.asarray(rng.randn(9, 2, 4, 8), jnp.float32)
        pt = jnp.asarray(rng.randint(0, 9, (2, 4)), jnp.int32)
        lens = jnp.asarray([3, 14], jnp.int32)
        from repro.kernels.flash_decode.ops import paged_decode_attention

        out_tuned = paged_decode_attention(q, kp, vp, lens, pt,
                                           impl="stream")
        out_explicit = paged_decode_attention(q, kp, vp, lens, pt,
                                              impl="stream",
                                              pages_per_program=2)
        np.testing.assert_array_equal(np.asarray(out_tuned),
                                      np.asarray(out_explicit))
    finally:
        tune.reset_default_cache()
