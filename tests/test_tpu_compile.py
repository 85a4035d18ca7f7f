"""Compile the served path's kernels for a TPU v5e that is described, not
attached.

Interpret mode on the CPU checks the kernels' results; it cannot show
what Mosaic refuses (a ``dynamic_slice`` on a vector, a block that does
not fit VMEM).  These tests run the TPU compiler here, at the paper's
tree sizes, and assert that each program really contains the compiled
kernel (``tpu_custom_call``).  Nothing runs, so they say nothing about
results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every test
worker imports this file.
"""

import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import gomoku_cfg, pong  # noqa: E402
from repro.core.fused import _fused_program  # noqa: E402
from repro.core.tree import init_arena, stored_rows  # noqa: E402
from repro.envs import BanditTreeEnv, BanditValueBackend  # noqa: E402
from repro.kernels import uct_backup, uct_select  # noqa: E402

P = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _arena(cfg, G, sharding):
    return _on(sharding, jax.eval_shape(lambda: init_arena(cfg, G)))


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compiled_text(lowered):
    compiled = lowered.compile()   # raises what the chip's compiler raises
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _select(cfg, G, sharding):
    return uct_select.select_arena.lower(
        cfg, _arena(cfg, G, sharding), _i32(sharding, G), P,
        interpret=False)


def _backup(cfg, G, sharding):
    D = cfg.D
    return uct_backup.backup_arena.lower(
        cfg, _arena(cfg, G, sharding), _i32(sharding, G),
        _i32(sharding, G, P, D), _i32(sharding, G, P, D),
        _i32(sharding, G, P), _i32(sharding, G, P), _i32(sharding, G, P),
        _i32(sharding, G, P), _i32(sharding, G, P),
        p=P, alternating=cfg.expand_all, interpret=False)


KERNELS = {"select": _select, "backup": _backup}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("G", [1, 4])
def test_uct_kernels_compile_pong_paper_size(one_chip, kernel, G):
    """Pong at the paper's size (X=56,000, F=6, D=9), p=16 workers."""
    text = _compiled_text(KERNELS[kernel](pong.TREE, G, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_uct_kernels_compile_gomoku_paper_size(one_chip, kernel):
    """Gomoku at the paper's size (X=48,000, F=36 padded to 64, D=5) at
    G=1: one slot's UCT is the most a launch can hold in VMEM today
    (G=2 already runs out; see ROADMAP S4)."""
    text = _compiled_text(KERNELS[kernel](gomoku_cfg.TREE, 1, one_chip))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def fused_pong_text(one_chip):
    """The fused K=4 superstep program on the pallas variant, G=4, at Pong
    size, compiled once for the module.  Its kernels go through
    kernels.ops, which picks compiled kernels because the program is
    lowered for the TPU the arguments are placed on — the test steers the
    platform only through that placement."""
    cfg, G, K = pong.TREE, 4, 4
    env = BanditTreeEnv(fanout=cfg.F, terminal_depth=cfg.D + 1)
    sim = BanditValueBackend()
    states = jax.ShapeDtypeStruct((G, cfg.X) + env.state_shape, jnp.float32,
                                  sharding=one_chip)
    lowered = _fused_program.lower(
        cfg, "pallas", P, K, env, sim, False,
        _arena(cfg, G, one_chip), states,
        jax.ShapeDtypeStruct((G,), jnp.bool_, sharding=one_chip),
        _i32(one_chip, G))
    return _compiled_text(lowered)


def test_fused_pallas_program_compiles_pong_paper_size(fused_pong_text):
    assert "tpu_custom_call" in fused_pong_text
    assert "while" in fused_pong_text


def test_fused_program_kernel_names_match_the_reader(fused_pong_text):
    """The two Mosaic custom calls carry the names their `pallas_call`s
    give them, and those are the names the benchmark's kernel reader
    (`uct_kernel_us_per_superstep`) matches in a device trace."""
    from perfbench import spec

    reader = spec.load_module(
        spec.reader_path("uct_kernel_us_per_superstep"), "reader")
    names = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
        fused_pong_text, re.M)]
    assert len(names) == 2, names
    assert all(reader.KERNELS.match(n) for n in names), names
    assert sorted(n.split(".")[0] for n in names) == ["backup_arena",
                                                      "select_arena"]


def test_fused_program_keeps_the_arena_in_the_kernels_layout(fused_pong_text):
    """Every arena-sized value of the compiled Pong program is in the
    row-major layout the two kernels read, and none has the logical
    [G, X, Fp] / [G, X] shape: the TPU compiler relays nothing out
    around select and backup (the state image, f32[G, X, 8], is not the
    arena)."""
    cfg, G = pong.TREE, 4
    er, nr, lr = stored_rows(cfg.X, cfg.Fp)
    arena = re.compile(
        rf"(s32\[{G},({er}|{nr}),128\]|f32\[{G},{lr},128\])\{{([0-9,]+)")
    logical = re.compile(rf"s32\[{G},{cfg.X}(,{cfg.Fp})?\]")
    layouts = {m.group(3) for m in arena.finditer(fused_pong_text)}
    assert layouts == {"2,1,0"}, layouts
    assert not logical.search(fused_pong_text)
