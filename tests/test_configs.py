"""Config registry + param accounting vs published sizes."""
import pytest

from repro.configs import (SHAPES, get_config, get_reduced_config,
                           iter_cells, list_archs, shape_applicable)

PUBLISHED_B = {
    "grok-1-314b": (314, 0.08), "deepseek-moe-16b": (16.4, 0.05),
    "whisper-medium": (0.769, 0.10), "nemotron-4-15b": (15.0, 0.08),
    "qwen2.5-32b": (32.8, 0.05), "qwen3-4b": (4.0, 0.05),
    "deepseek-7b": (6.9, 0.05), "hymba-1.5b": (1.5, 0.15),
    "llama-3.2-vision-90b": (90, 0.05), "rwkv6-3b": (3.1, 0.05),
    "llama3.1-8b": (8.0, 0.05), "mistral-7b": (7.2, 0.05),
    "deepseek-v2-lite": (15.7, 0.05),
}


@pytest.mark.parametrize("arch", list_archs())
def test_param_count_matches_published(arch):
    if arch not in PUBLISHED_B:
        pytest.skip("no published reference")
    cfg = get_config(arch)
    n = cfg.param_count() / 1e9
    ref, tol = PUBLISHED_B[arch]
    assert abs(n - ref) / ref < tol, f"{arch}: {n:.2f}B vs {ref}B"


def test_moe_active_less_than_total():
    for arch in ("grok-1-314b", "deepseek-moe-16b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.5 * cfg.param_count()


def test_cell_grid():
    cells = list(iter_cells())
    assert len(cells) == 40                      # 10 archs x 4 shapes
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(runnable) == 32
    assert all(s == "long_500k" for _, s, ok in skipped)
    # long_500k runs exactly for the sub-quadratic archs
    long_ok = {a for a, s, ok in cells if s == "long_500k" and ok}
    assert long_ok == {"rwkv6-3b", "hymba-1.5b"}


@pytest.mark.parametrize("arch", list_archs())
def test_reduced_configs_are_small(arch):
    r = get_reduced_config(arch)
    assert r.param_count() < 5e6
    assert r.family == get_config(arch).family


def test_shapes_table():
    assert SHAPES["train_4k"].tokens == 4096 * 256
    assert SHAPES["long_500k"].global_batch == 1
    assert SHAPES["decode_32k"].kind == "decode"


def test_unknown_arch_raises():
    with pytest.raises(KeyError):
        get_config("nonexistent-1b")
