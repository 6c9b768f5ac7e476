"""System-level invariants: the 40-cell grid, config exactness, padding."""
import jax
import pytest

from repro.configs.registry import ARCH_IDS, SHAPES, all_cells, get_config


def test_grid_is_40_cells():
    cells = all_cells()
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    # long_500k runs only for the sub-quadratic archs
    assert len(skipped) == 8
    assert all(s == "long_500k" for _, s, ok, _ in skipped)
    assert {a for a, s, ok, _ in cells if s == "long_500k" and ok} == {
        "mamba2-780m", "recurrentgemma-9b"}


def test_every_arch_importable_and_padded():
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.padded_vocab % 128 == 0
        assert cfg.padded_vocab - cfg.vocab < 128
        if cfg.n_experts >= 16:
            assert cfg.padded_experts % 16 == 0
        assert cfg.param_count() > 0


def test_shapes_match_assignment():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].seq_len == 32768
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


def test_production_mesh_axes():
    """The assigned mesh layouts (AbstractMesh: no device init)."""
    from jax.sharding import AbstractMesh

    single = AbstractMesh((16, 16), ("data", "model"))
    multi = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert dict(single.shape) == {"data": 16, "model": 16}
    assert dict(multi.shape) == {"pod": 2, "data": 16, "model": 16}
