"""Compile the Pallas kernels for a TPU v5e that is described, not attached.

Interpret mode on the CPU checks a kernel's results but not whether the TPU
compiler accepts it: block shapes off the (8, 128) tiling, vector ops the
chip lacks (v5e has no int8 vector compare) and VMEM overruns only surface
here.  Every kernel of the main path is compiled with ``interpret=False`` at
the paper's widths (n = m = 1002, and 2392), plus the vmapped forms the
batched engine runs.  Nothing runs, so nothing about results or speed is
checked.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (choice_info, fused_select, pheromone_update,
                           sparse_select, tour_select, two_opt)

f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    an executable compiled for an absent chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1002, 2392])
@pytest.mark.parametrize("tau_dtype", ["fp32", "int8"])
def test_fused_select_compiles(one_chip, n, tau_dtype):
    m = n
    if tau_dtype == "int8":
        fn = lambda t, s, e, c, v, r: fused_select.fused_select(
            t, e, c, v, r, tau_scale=s, interpret=False)
        tau = [((n, n), i8), ((n, 1), f32)]
    else:
        fn = functools.partial(fused_select.fused_select, interpret=False)
        tau = [((n, n), f32)]
    c = _compile(fn, one_chip, *tau, ((n, n), f32), ((m,), i32),
                 ((m, n), jnp.bool_), ((m, n), f32))
    assert _has_kernel(c)


@pytest.mark.parametrize("n", [1002, 2392])
def test_sparse_select_compiles(one_chip, n):
    m, k = n, 32
    fn = functools.partial(sparse_select.sparse_select, interpret=False)
    c = _compile(fn, one_chip, ((m, k), f32), ((m, k), f32), ((m, k), i32),
                 ((m, n), jnp.bool_), ((m, n), f32))
    assert _has_kernel(c)


@pytest.mark.parametrize("n", [1002, 2392])
def test_choice_info_compiles(one_chip, n):
    fn = functools.partial(choice_info.choice_info, interpret=False)
    assert _has_kernel(_compile(fn, one_chip, ((n, n), f32), ((n, n), f32)))


def test_tour_select_compiles(one_chip):
    m = n = 1002
    fn = functools.partial(tour_select.tour_select, interpret=False)
    c = _compile(fn, one_chip, ((m, n), f32), ((m, n), jnp.bool_),
                 ((m, n), f32))
    assert _has_kernel(c)


def test_pheromone_update_compiles(one_chip):
    n = m = 1002
    e = 2 * m * n
    fn = lambda t, f, to, w: pheromone_update.pheromone_update(
        t, f, to, w, 0.5, interpret=False)
    c = _compile(fn, one_chip, ((n, n), f32), ((e,), i32), ((e,), i32),
                 ((e,), f32))
    assert _has_kernel(c)


@pytest.mark.parametrize("mode", ["best", "first"])
def test_two_opt_best_compiles(one_chip, mode):
    m = n = 1002
    moves = n * 8
    fn = functools.partial(two_opt.two_opt_best, mode=mode, interpret=False)
    shape = ((m, moves), f32)
    c = _compile(fn, one_chip, shape, shape, shape, shape,
                 ((m, moves), jnp.bool_))
    assert _has_kernel(c)


# The batched engine vmaps the per-colony step, which gives every block a
# leading squeezed dim; the TPU's rules then apply to the last two dims.
def test_vmapped_fused_select_compiles(one_chip):
    b, n = 8, 1024
    fn = jax.vmap(functools.partial(fused_select.fused_select,
                                    interpret=False))
    c = _compile(fn, one_chip, ((b, n, n), f32), ((b, n, n), f32),
                 ((b, n), i32), ((b, n, n), jnp.bool_), ((b, n, n), f32))
    assert _has_kernel(c)


def test_vmapped_pheromone_update_compiles(one_chip):
    b, n = 8, 64
    e = 2 * n * n
    fn = jax.vmap(lambda t, f, to, w: pheromone_update.pheromone_update(
        t, f, to, w, 0.5, interpret=False))
    c = _compile(fn, one_chip, ((b, n, n), f32), ((b, e), i32),
                 ((b, e), i32), ((b, e), f32))
    assert _has_kernel(c)


def test_engine_batch_program_fits_one_chip(one_chip):
    """The streaming pool's chunk program at bucket 1024 with 8 slots fits
    the chip's 16 GB: gathering whole distance rows per tour position had
    made it a 32 GiB temporary."""
    from repro.core import aco
    from repro.solver import engine, programs
    cfg = aco.ACOConfig(iterations=20, variant="mmas")
    problem, states, budgets, since, mets, ewt = \
        programs.ProgramCache()._templates(1024, 8, cfg, "dense", False)
    spec = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    compiled = engine.aot_lower(spec(problem), spec(states), spec(budgets),
                                cfg, 4, 0, spec(since), mets, kind="dense",
                                ewt=ewt, donate=True).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30
