"""CPU tests of ``chip_smoke.py``: it refuses to run without a TPU, its
phases pass their own checks at a tiny size (NPB at 4 ranks, the quick
mixed family), and the compile cache helper picks its directory as
documented."""

import json
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    # The repo root stays importable while the module runs: the spawned
    # reference workers import chip_smoke, and the family grid comes
    # from benchmarks/.
    sys.path.insert(0, str(ROOT))
    import chip_smoke

    yield chip_smoke
    sys.path.remove(str(ROOT))


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke.Clock()


@pytest.fixture(scope="module")
def npb_sweep(smoke, clock):
    return smoke.sweep_phase(clock, "npb", smoke.npb_scenarios(nodes=(4,)))


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after a test sets it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_main_refuses_cpu(smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and "no CPU fallback" in err
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_sweep_phase_warm_run_compiles_nothing(npb_sweep, capsys):
    assert len(npb_sweep) == 3 * 2 * 2            # IS/EP/CG x bounds x pols
    assert npb_sweep.profile.compiles == 0         # the warm run
    assert {r.backend for r in npb_sweep} == {"jax"}


def test_reference_phase_holds_envelope(smoke, clock, npb_sweep, capsys):
    quick = smoke.sweep_phase(clock, "quick", smoke.family_grid(quick=True))
    pairs = smoke.reference_pairs(quick, npb_sweep)
    # quick family in full + first bound of every NPB member
    assert len(pairs) == len(quick) + 3 * 2
    worst = smoke.reference_phase(clock, pairs, max_workers=2)
    assert worst["dmakespan_s"] <= smoke.MAKESPAN_ATOL
    assert worst["denergy_rel"] <= smoke.ENERGY_RTOL
    out = capsys.readouterr().out
    assert "c.reference: cells=" in out and "jax_in_workers=0" in out


def test_reference_phase_rejects_a_wrong_result(smoke, clock, npb_sweep):
    import dataclasses

    rec = npb_sweep.records[0]
    wrong = dataclasses.replace(rec.result,
                                makespan=rec.result.makespan + 1.0)
    other = npb_sweep.records[1]
    with pytest.raises(smoke.SmokeError, match="outside the envelope"):
        smoke.reference_phase(clock, [(rec.scenario, wrong),
                                      (other.scenario, other.result)],
                              max_workers=2)


def test_service_phase_small(smoke, clock, tmp_path, monkeypatch, capsys):
    # With the variable set the entry points leave JAX's cache alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    from repro.cluster.cli import main as cluster_main

    arrivals = tmp_path / "arrivals.jsonl"
    assert cluster_main(["generate", "--pool", "mixed", "--jobs", "12",
                         "--rate-hz", "0.3", "--seed", "7",
                         "--out", str(arrivals)]) == 0
    smoke.service_phase(clock, arrivals=arrivals, nodes=10)
    out = capsys.readouterr().out
    assert "e.serve: rc=0" in out and "e.cluster: rc=0" in out
    assert jax.config.jax_compilation_cache_dir is None
    assert not (tmp_path / "cc").exists()


def test_diff_phase(smoke, clock, capsys):
    smoke.diff_phase(clock, steps=1)
    assert "f.diff: steps=3" in capsys.readouterr().out


def test_compile_cache_honours_env(tmp_path, monkeypatch, cache_config):
    from repro.backends.jax import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    from repro.backends.jax import CHECKOUT_CACHE_DIR, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    for _ in range(2):
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
