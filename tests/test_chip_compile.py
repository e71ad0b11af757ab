"""Compile the serving path's chip programs for a described TPU v5e.

Nothing runs here: the TPU compiler, which ships with jax, compiles for
a chip that is described and not attached, and refuses what the chip
would refuse (HBM refs touched without a DMA, slices off the tiling,
too much VMEM).  Interpret mode accepts all of those, so these tests
guard the fused page kernels at qwen2-0.5b's published widths, where
the CPU parity tests cannot.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import page_install as pi
from repro.models import lm
from repro.models import transformer as T

ARCH = "qwen2-0.5b"
SLOTS = 4           # the serve CLI's default slot count
GROUP = 2           # pages per staged fetch group


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _layout(cfg, batch, max_len):
    single = jax.eval_shape(lambda: T.init_cache(cfg, 1, max_len))
    full = jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len))
    return pi.page_layout(single, full, batch)


def _on(sharding, tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _assert_kernel(compiled):
    # the Pallas kernel is in the program, not replaced by XLA ops
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("max_len", [128, 2048])
def test_install_pages_compiles_for_v5e(one_chip, no_compile_cache,
                                        max_len):
    layout = _layout(get_config(ARCH), SLOTS, max_len)
    assert layout.kernel_leaves(), "no leaf reaches the kernel"
    words = layout.page_bytes // layout.word_bytes
    batch = tuple(_on(one_chip, jax.ShapeDtypeStruct(sp.batch_shape,
                                                     sp.dtype))
                  for sp in layout.leaves)
    # the TieredStore hand-off: one staged (G, words) group, a row each
    group = _on(one_chip, jax.ShapeDtypeStruct((GROUP, words),
                                               layout.word_dtype))
    bufs = (group,) * GROUP
    vec = _on(one_chip, jax.ShapeDtypeStruct((GROUP,), jnp.int32))
    fn = pi._install_pallas(layout, tuple(b.shape for b in bufs),
                            n_buffers=2, interpret=False, donate=True)
    compiled = fn.lower(batch, bufs, vec, vec).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("max_len", [128, 2048])
def test_pack_page_compiles_for_v5e(one_chip, no_compile_cache, max_len):
    layout = _layout(get_config(ARCH), SLOTS, max_len)
    leaves = tuple(_on(one_chip, jax.ShapeDtypeStruct(sp.shape, sp.dtype))
                   for sp in layout.leaves)
    compiled = pi._pack_pallas(layout, interpret=False).lower(
        leaves).compile()
    _assert_kernel(compiled)
    out = compiled.out_info
    assert out.shape == (layout.page_bytes // layout.word_bytes,)


@pytest.mark.parametrize("kernel", ["kv_install_pages", "kv_pack_page"])
def test_page_kernels_carry_their_names(one_chip, no_compile_cache, kernel):
    """The device trace names a kernel by its HLO op, which takes the
    ``pallas_call``'s ``name``: ``kv_install_pages.N``, not
    ``pallas_call.N``."""
    layout = _layout(get_config(ARCH), SLOTS, 128)
    if kernel == "kv_pack_page":
        leaves = tuple(_on(one_chip, jax.ShapeDtypeStruct(sp.shape,
                                                          sp.dtype))
                       for sp in layout.leaves)
        lowered = pi._pack_pallas(layout, interpret=False).lower(leaves)
    else:
        words = layout.page_bytes // layout.word_bytes
        batch = tuple(_on(one_chip, jax.ShapeDtypeStruct(sp.batch_shape,
                                                         sp.dtype))
                      for sp in layout.leaves)
        bufs = (_on(one_chip, jax.ShapeDtypeStruct((GROUP, words),
                                                   layout.word_dtype)),)
        vec = _on(one_chip, jax.ShapeDtypeStruct((1,), jnp.int32))
        lowered = pi._install_pallas(layout, ((GROUP, words),),
                                     n_buffers=2, interpret=False,
                                     donate=True).lower(batch, bufs, vec,
                                                        vec)
    assert kernel in lowered.as_text()
    calls = [line.split(" = ", 1)[0].strip()
             for line in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(c.startswith(f"%{kernel}.") for c in calls), calls


def test_full_width_decode_step_compiles_for_v5e(one_chip,
                                                 no_compile_cache):
    cfg = get_config(ARCH)
    B, max_len = 8, 2048
    params = _on(one_chip, jax.eval_shape(
        lambda: T.tree_init(T.param_defs(cfg), cfg, jax.random.PRNGKey(0))))
    caches = _on(one_chip, jax.eval_shape(
        lambda: T.init_cache(cfg, B, max_len)))
    batch = _on(one_chip, {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((B, 1), jnp.int32)})
    compiled = jax.jit(lm.make_decode_step(cfg)).lower(
        params, batch, caches).compile()
    mem = compiled.memory_analysis()
    # weights + KV cache + temporaries fit one v5e's 16 GB of HBM
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_in_place_decode_copies_no_cache_for_v5e(one_chip, no_compile_cache):
    """The engine's decode writes its KV rows into the donated cache: the
    program aliases every cache leaf to its output and neither copies a
    whole leaf nor keeps a second cache among its temporaries."""
    from repro.serving.engine import _jitted_steps
    cfg = get_config(ARCH)
    B, max_len = 8, 2048
    params = _on(one_chip, jax.eval_shape(
        lambda: T.tree_init(T.param_defs(cfg), cfg, jax.random.PRNGKey(0))))
    caches = _on(one_chip, jax.eval_shape(
        lambda: T.init_cache(cfg, B, max_len)))
    batch = _on(one_chip, {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((B, 1), jnp.int32)})
    compiled = _jitted_steps(cfg)[2].lower(params, batch, caches).compile()
    cache_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 0.01 * cache_bytes
    leaf = "[{}]".format(",".join(
        map(str, caches["groups"]["b0"]["attn"]["k"].shape)))
    copies = [line for line in compiled.as_text().splitlines()
              if " copy(" in line and leaf in line.split(" copy(")[0]]
    assert not copies, copies[:2]
