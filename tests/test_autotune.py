"""Autotuner tests: cache lookups (env repoint / disable / fallback; the
kernels make any tile TPU-legal), engine roofline rows, artifact production, and the
differential-numerics invariant — every block size the tuner may select
yields the same kernel output (bitwise where the padded shape is the same,
to a stated tolerance elsewhere), so autotuning can change performance but
not results."""
import json

import jax
import numpy as np
import pytest

from repro.kernels.tiling import VMEM_BUDGET_BYTES
from repro.launch import autotune
from repro.launch.autotune import (ENCOUNTER_BLOCK_D_CANDIDATES,
                                   ENCOUNTER_BLOCK_M_CANDIDATES,
                                   MULE_AGG_BLOCK_D_CANDIDATES,
                                   analyze_engine_step, tuned_block_d,
                                   tuned_encounter_blocks,
                                   tuning_cache_clear)


@pytest.fixture(autouse=True)
def _fresh_cache():
    # the lookup memoizes the default-resolution cache; tests repoint
    # REPRO_TUNE_CACHE, so drop the memo on both sides of every test
    tuning_cache_clear()
    yield
    tuning_cache_clear()


def _write_cache(path, tuned):
    path.write_text(json.dumps(
        {"bench": "autotune.run_roofline", "config": {}, "roofline": [],
         "tuned": tuned, "tuned_speedup_vs_default": 1.0}))


# ---------------------------------------------------------------------------
# tuning-cache lookup
# ---------------------------------------------------------------------------


def test_cache_lookup_nearest_shape(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    _write_cache(cache, {
        "mule_agg": [{"f": 8, "m": 64, "d": 4096, "block_d": 512},
                     {"f": 8, "m": 64, "d": 65536, "block_d": 2048}],
        "encounter_mix": [
            {"m": 512, "d": 480, "block_m": 128, "block_d": 256},
            {"m": 4096, "d": 480, "block_m": 512, "block_d": 1024}]})
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    assert tuned_block_d(4000) == 512          # nearest |log d ratio|
    assert tuned_block_d(1 << 17) == 2048
    assert tuned_encounter_blocks(600, 480) == (128, 256)
    assert tuned_encounter_blocks(3000, 480) == (512, 1024)


def test_kernels_legalize_whatever_the_cache_returns(tmp_path, monkeypatch):
    """The lookups hand back what the cache stores (here tiles clamped to a
    small tuning shape); the kernels turn it into tiles the TPU lowering
    accepts at the queried shape: block_d a multiple of 128 or the whole D,
    block_m and the mule tile a multiple of 8 or the whole M."""
    from repro.kernels.encounter_mix import kernel as enc
    from repro.kernels.mule_agg import kernel as agg
    cache = tmp_path / "cache.json"
    _write_cache(cache, {
        "mule_agg": [{"f": 8, "m": 64, "d": 500, "block_d": 500}],
        "encounter_mix": [{"m": 300, "d": 480, "block_m": 300,
                           "block_d": 480}]})
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    assert tuned_block_d(546_484) == 500
    assert tuned_encounter_blocks(512, 546_484) == (300, 480)
    for m, d in ((64, 500), (64, 520), (1100, 546_484)):
        bk, bd = agg.fit_blocks(8, m, d, tuned_block_d(d))
        assert bd == d or bd % 128 == 0, (d, bd)
        assert bk == m or bk % 8 == 0, (m, bk)
    for m, d in ((300, 480), (512, 546_484), (1024, 520), (20, 130)):
        bm, bk, bd = enc.fit_blocks(m, m, d, *tuned_encounter_blocks(m, d))
        assert bm == m or bm % 8 == 0, (m, bm)
        assert bk == m or bk % 8 == 0, (m, bk)
        assert bd == d or bd % 128 == 0, (d, bd)
    assert enc.fit_blocks(512, 512, 546_484,
                          *tuned_encounter_blocks(512, 546_484)) \
        == (296, 512, 384)


def test_cache_env_empty_disables(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "")
    tuning_cache_clear()
    assert tuned_block_d(1 << 18) == autotune.MULE_AGG_DEFAULT_BLOCK_D
    assert tuned_encounter_blocks(1024, 4800) == \
        autotune.ENCOUNTER_DEFAULT_BLOCKS


def test_cache_missing_or_malformed_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "absent.json"))
    tuning_cache_clear()
    assert tuned_block_d(4096) == autotune.MULE_AGG_DEFAULT_BLOCK_D
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(bad))
    tuning_cache_clear()
    assert tuned_encounter_blocks(640, 4096) == \
        autotune.ENCOUNTER_DEFAULT_BLOCKS
    # schema-valid JSON without a tuned section reads as "no cache" too
    bad.write_text(json.dumps({"tuned": "oops"}))
    tuning_cache_clear()
    assert tuned_block_d(4096) == autotune.MULE_AGG_DEFAULT_BLOCK_D


def test_committed_cache_drives_the_kernels():
    """The repo's own BENCH_roofline.json is what pick_block_d and the
    encounter wrapper consult by default."""
    from repro.kernels.mule_agg.ops import pick_block_d
    cache = autotune.load_tuning_cache()
    assert cache is not None, "committed BENCH_roofline.json must parse"
    entry = cache["tuned"]["mule_agg"][-1]
    assert pick_block_d(entry["d"]) == entry["block_d"]
    em = cache["tuned"]["encounter_mix"][0]
    assert tuned_encounter_blocks(em["m"], em["d"]) == \
        (em["block_m"], em["block_d"])


def test_explicit_block_beats_cache(tmp_path, monkeypatch):
    from repro.kernels.mule_agg.ops import mule_agg, pick_block_d
    cache = tmp_path / "cache.json"
    _write_cache(cache, {"mule_agg": [{"f": 2, "m": 8, "d": 256,
                                       "block_d": 256}],
                         "encounter_mix": []})
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    assert pick_block_d(256) == 256
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.uniform(k1, (2, 8))
    w = jax.random.normal(k2, (8, 256))
    ref = np.asarray(mule_agg(a, w, backend="ref"))
    out = np.asarray(mule_agg(a, w, block_d=128, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# differential numerics: tuning may move blocks, results must not move
# ---------------------------------------------------------------------------


def test_mule_agg_bitwise_identical_across_candidates():
    f, m, d = 4, 24, 1000                      # d indivisible by every block
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    assign = jax.random.uniform(k1, (f, m))
    w = jax.random.normal(k2, (m, d))
    from repro.kernels.mule_agg.kernel import fit_blocks, mule_agg_pallas
    from repro.kernels.mule_agg.ref import mule_agg_reference
    # D tiles never change the contraction (it runs over mules), so every
    # candidate is pinned bitwise
    blocks = sorted({fit_blocks(f, m, d, b)[1]
                     for b in MULE_AGG_BLOCK_D_CANDIDATES})
    assert len(blocks) >= 3                    # a real sweep, not one cell
    assert all(b == d or b % 128 == 0 for b in blocks)
    outs = [np.asarray(mule_agg_pallas(assign, w, block_d=b, interpret=True))
            for b in blocks]
    for b, o in zip(blocks[1:], outs[1:]):
        assert np.array_equal(outs[0], o), f"block_d={b} changed the output"
    np.testing.assert_allclose(
        outs[0], np.asarray(mule_agg_reference(assign, w)),
        rtol=2e-5, atol=2e-5)


def test_encounter_mix_bitwise_identical_across_candidates():
    # M fits one contraction tile, so every candidate reduces over the same
    # peers in the same order; D stays indivisible to exercise ragged
    # column tiles. Candidates whose padded D is the same run the same
    # matmul shapes and are pinned bitwise; across padded widths the CPU
    # interpreter's matmul may round differently (its blocking follows the
    # operand width), so those agree to 1e-6 — a few f32 ulps of values of
    # order 1, far below the 2e-5 oracle tolerance below.
    m, d = 512, 520
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    pos = jax.random.uniform(ks[0], (m, 2))
    area = jax.random.randint(ks[1], (m,), 0, 2)
    active = jax.random.uniform(ks[2], (m,)) < 0.9
    w = jax.random.normal(ks[3], (m, d))
    from repro.kernels.encounter_mix.kernel import (encounter_mix_pallas,
                                                    fit_blocks)
    from repro.kernels.encounter_mix.ref import encounter_mix_reference
    pairs = sorted({fit_blocks(m, m, d, bm, bd)[::2]
                    for bm in ENCOUNTER_BLOCK_M_CANDIDATES
                    for bd in ENCOUNTER_BLOCK_D_CANDIDATES})
    assert len(pairs) >= 4
    assert all(bm % 8 == 0 and (bd == d or bd % 128 == 0)
               for bm, bd in pairs)
    outs = {}
    for bm, bd in pairs:
        mix, mass = encounter_mix_pallas(pos, area, active, w, radius=0.12,
                                         block_m=bm, block_d=bd,
                                         interpret=True)
        outs[bm, bd] = (np.asarray(mix), np.asarray(mass))
    first = {}
    for (bm, bd), (mix, mass) in outs.items():
        d_pad = -(-d // bd) * bd
        if d_pad in first:
            assert np.array_equal(first[d_pad][0], mix), \
                f"blocks ({bm},{bd}) changed the mix"
        else:
            first[d_pad] = (mix, mass)
        base_mix, base_mass = outs[pairs[0]]
        assert np.array_equal(base_mass, mass), \
            f"blocks ({bm},{bd}) changed the mass"
        np.testing.assert_allclose(mix, base_mix, rtol=1e-6, atol=1e-6)
    outs = [outs[p] for p in pairs]
    ref_mix, ref_mass = encounter_mix_reference(pos, area, active, w,
                                                radius=0.12)
    np.testing.assert_allclose(outs[0][1], np.asarray(ref_mass),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(outs[0][0], np.asarray(ref_mix),
                               rtol=2e-5, atol=2e-5)


def test_encounter_mix_padded_rows_still_exact_vs_reference():
    # when block_m does NOT divide M the zero-padded contraction length
    # differs per candidate — bitwise identity is then out of reach on CPU
    # (reduction order), but every candidate must still match the oracle
    m, d = 300, 520
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    pos = jax.random.uniform(ks[0], (m, 2))
    area = jax.random.randint(ks[1], (m,), 0, 2)
    active = jax.random.uniform(ks[2], (m,)) < 0.9
    w = jax.random.normal(ks[3], (m, d))
    from repro.kernels.encounter_mix.kernel import encounter_mix_pallas
    from repro.kernels.encounter_mix.ref import encounter_mix_reference
    ref_mix, ref_mass = encounter_mix_reference(pos, area, active, w,
                                                radius=0.12)
    for bm in (128, 256, 300):
        mix, mass = encounter_mix_pallas(pos, area, active, w, radius=0.12,
                                         block_m=bm, block_d=256,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(mass), np.asarray(ref_mass),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(mix), np.asarray(ref_mix),
                                   rtol=2e-5, atol=2e-5)


def test_tile_models_fit_the_budget():
    from repro.kernels.encounter_mix import kernel as enc
    from repro.kernels.mule_agg import kernel as agg
    # every candidate the tuner sweeps is fitted into the VMEM budget, at
    # the tuning shapes and at the published mule-CNN width alike
    for m, d in ((64, 65536), (512, 546_484), (4096, 546_484)):
        for b in MULE_AGG_BLOCK_D_CANDIDATES:
            bk, bd = agg.fit_blocks(8, m, d, b)
            assert agg.tile_bytes(8, bk, bd) <= VMEM_BUDGET_BYTES
        for bm in ENCOUNTER_BLOCK_M_CANDIDATES:
            for b in ENCOUNTER_BLOCK_D_CANDIDATES:
                tm, tk, td = enc.fit_blocks(m, m, d, bm, b)
                assert enc.tile_bytes(tm, tk, td) <= VMEM_BUDGET_BYTES
    # and the models are monotone in each tile dim
    assert agg.tile_bytes(8, 512, 512) < agg.tile_bytes(8, 512, 1024)
    assert agg.tile_bytes(8, 256, 512) < agg.tile_bytes(8, 512, 512)
    assert enc.tile_bytes(128, 512, 256) < enc.tile_bytes(256, 512, 256)
    assert enc.tile_bytes(128, 256, 256) < enc.tile_bytes(128, 512, 256)
    # a shape no legal tile fits is refused before lowering, by name
    with pytest.raises(ValueError, match="F=100000"):
        agg.fit_blocks(100_000, 512, 546_484, 2048)


# ---------------------------------------------------------------------------
# engine roofline + artifact production
# ---------------------------------------------------------------------------


def test_analyze_engine_step_terms():
    row = analyze_engine_step("mlmule", n_mules=8, steps=6)
    assert row["method"] == "mlmule"
    assert row["mesh"] == "1" and row["chips"] == 1
    assert row["flops_per_device"] > 0
    assert row["bytes_per_device"] > 0
    assert row["coll_bytes_per_device"] == 0   # single host: no collectives
    assert row["dominant"] in ("compute", "memory", "collective")
    assert row["t_memory_us_per_step"] == pytest.approx(
        row["t_memory_s"] / 6 * 1e6)


def test_run_roofline_artifact_validates(tmp_path):
    """A freshly produced artifact satisfies the bench_gate schema and
    round-trips through the regression gate against itself."""
    from benchmarks import bench_gate
    out = tmp_path / "BENCH_roofline.json"
    payload = autotune.run_roofline(
        str(out), reps=1, steps=4, mule_counts=(8,), methods=("local",),
        mule_agg_shapes=((2, 8, 512),), encounter_shapes=((64, 96),))
    schema = bench_gate.validate("BENCH_roofline.json", payload)
    assert schema.headline == "tuned_speedup_vs_default"
    on_disk = json.loads(out.read_text())
    assert on_disk["tuned_speedup_vs_default"] == \
        payload["tuned_speedup_vs_default"]
    rows = on_disk["roofline"]
    assert [r["method"] for r in rows] == ["local"]
    assert rows[0]["n_mules"] == 8
    # the gate passes an artifact against itself, always
    assert bench_gate.gate_artifact("BENCH_roofline.json",
                                    on_disk, payload).ok


# ---------------------------------------------------------------------------
# roofline-driven mesh-shape suggestion
# ---------------------------------------------------------------------------


def _mesh_row(mesh, method, n_mules, coll, mem):
    return {"mesh": mesh, "method": method, "n_mules": n_mules,
            "t_collective_us_per_step": coll, "t_memory_us_per_step": mem}


def _write_mesh_cache(path, rows):
    path.write_text(json.dumps(
        {"bench": "autotune.run_roofline", "config": {}, "roofline": rows,
         "tuned": {"mule_agg": [], "encounter_mix": []},
         "tuned_speedup_vs_default": 1.0}))


def test_suggest_mesh_shape_minimizes_coll_plus_mem(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    _write_mesh_cache(cache, [
        _mesh_row("1x8", "gossip", 64, 10.0, 5.0),     # cost 15
        _mesh_row("2x4", "gossip", 64, 4.0, 5.0),      # cost 9  <- min
        _mesh_row("4x2", "gossip", 64, 9.0, 9.0),      # cost 18
        _mesh_row("1", "gossip", 64, 0.0, 0.0),        # host row: not a shape
    ])
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    assert autotune.suggest_mesh_shape("gossip", 64) == (2, 4)


def test_suggest_mesh_shape_method_filter_and_fallback(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    _write_mesh_cache(cache, [
        _mesh_row("1x8", "gossip", 64, 1.0, 1.0),
        _mesh_row("2x4", "oppcl", 64, 0.5, 0.5),
    ])
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    # rows for the method win even when another method's row is cheaper
    assert autotune.suggest_mesh_shape("gossip", 64) == (1, 8)
    assert autotune.suggest_mesh_shape("oppcl", 64) == (2, 4)
    # unknown method falls back to all mesh rows -> global min
    assert autotune.suggest_mesh_shape("mlmule", 64) == (2, 4)


def test_suggest_mesh_shape_nearest_population(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    _write_mesh_cache(cache, [
        _mesh_row("1x8", "gossip", 32, 1.0, 1.0),      # cheap at M=32
        _mesh_row("1x8", "gossip", 4096, 50.0, 50.0),  # dear at M=4096
        _mesh_row("2x4", "gossip", 32, 30.0, 30.0),
        _mesh_row("2x4", "gossip", 4096, 20.0, 20.0),
    ])
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    assert autotune.suggest_mesh_shape("gossip", 16) == (1, 8)
    assert autotune.suggest_mesh_shape("gossip", 8192) == (2, 4)


def test_suggest_mesh_shape_without_cache_or_rows(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "")
    tuning_cache_clear()
    assert autotune.suggest_mesh_shape("gossip", 64) is None
    cache = tmp_path / "cache.json"
    _write_mesh_cache(cache, [
        _mesh_row("1", "gossip", 64, 1.0, 1.0),        # host rows only
        {"mesh": "2x4", "method": "gossip", "n_mules": 64},  # terms missing
        _mesh_row("axb", "gossip", 64, 1.0, 1.0),      # unparseable shape
    ])
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    tuning_cache_clear()
    assert autotune.suggest_mesh_shape("gossip", 64) is None


def test_committed_roofline_suggests_a_real_shape():
    """The repo's committed artifact carries per-mesh rows; the suggestion
    must come back as a usable 8-chip shape for every peer method."""
    for method in ("gossip", "oppcl", "mlmule", "mlmule+gossip"):
        shape = autotune.suggest_mesh_shape(method, 64)
        assert shape is not None and shape[0] * shape[1] == 8, (method, shape)


def test_tune_handles_tiny_shapes():
    # candidates clamp exactly like the kernels; a shape smaller than every
    # candidate must still tune (regression: empty-candidate crash)
    r = autotune.tune_mule_agg(2, 8, 64, reps=1)
    assert r["block_d"] == 64                  # the whole D
    e = autotune.tune_encounter_mix(16, 32, reps=1)
    assert (e["block_m"], e["block_d"]) == (16, 32)
    assert e["speedup_vs_default"] >= 0
