"""``chip_smoke.py`` on the CPU: its phases at a tiny population and small
widths (kernels in interpret mode), its refusal to run without a TPU, and
the compile-cache helper the entry points call."""
import jax
import pytest

import chip_smoke
from repro.configs.mule_cnn import CNNConfig
from repro.launch import compile_cache

SMALL = CNNConfig(image_size=8, conv_features=(4, 8), hidden=16)


@pytest.fixture(scope="module")
def workload():
    return chip_smoke.make_workload(0, 8, 6, SMALL)


@pytest.fixture(scope="module")
def mlmule(workload):
    return chip_smoke.phase_mlmule(workload)


def test_phase_mlmule_replays_deterministically(mlmule):
    check, models = mlmule
    assert check.ok, check.line()
    assert "second replay bitwise equal" in check.detail
    assert all(l.shape[0] == 8 for l in jax.tree.leaves(models))


def test_phase_streamed_matches_materialized(workload, mlmule):
    check = chip_smoke.phase_streamed(workload, mlmule[1], chunk_len=3)
    assert check.ok, check.line()
    assert "bitwise equal" in check.detail       # exact on the CPU


def test_phase_gossip(workload, mlmule):
    check = chip_smoke.phase_gossip(workload, mlmule[1])
    assert check.ok, check.line()


def test_phase_kernels_interpreted(mlmule):
    from repro.baselines.gossip import flatten_population
    flat, _ = flatten_population(jax.device_put(mlmule[1]))
    checks = chip_smoke.phase_kernels(flat, 0, interpret=True)
    assert [c.name for c in checks] == ["d", "d"]
    assert all(c.ok for c in checks), [c.line() for c in checks]


def test_phase_kernels_interpreted_over_ragged_mule_tiles():
    ragged = jax.random.normal(jax.random.PRNGKey(3),
                               (chip_smoke.RAGGED_MULES, 200))
    checks = chip_smoke.phase_kernels(ragged, 0, interpret=True)
    assert all(c.ok for c in checks), [c.line() for c in checks]
    assert "(block_k, block_d)=(512, 200)" in checks[0].detail


def test_phase_cpu_parity_is_exact_on_cpu():
    checks = chip_smoke.phase_cpu_parity(0, n_mules=8, n_steps=6, cnn=SMALL)
    assert [c.ok for c in checks] == [True, True], [c.line() for c in checks]
    assert all("bitwise equal" in c.detail for c in checks)


def test_phase_four_chips_on_one_device():
    # the sharded path end to end on a 1x1 "wide" mesh; the four-device
    # layout itself is what the chip run checks
    checks = chip_smoke.phase_four_chips(0, 6, n_mules=8, big_mules=8,
                                         n_chips=1, cnn=SMALL)
    assert len(checks) == 4 and all(c.ok for c in checks), \
        [c.line() for c in checks]


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch,
                                               restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: the helper sets nothing else
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_inside_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == path
    assert path.startswith(compile_cache.REPO_ROOT)
