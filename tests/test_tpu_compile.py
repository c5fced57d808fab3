"""Compile every registered Pallas kernel for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached. Each case runs at the widths its caller uses
and asserts the Mosaic kernel is in the compiled program
(``tpu_custom_call``), so a block misaligned to the chip's tiling, a
primitive the TPU lowering lacks or an over-budget memory space fails here
and not on the chip. The topology is described inside a fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.machine import resolve
from repro.kernels import (KERNEL_REGISTRY, bag_combine, bsr_spmm,
                           bucket_assign, flash_attention, gather_combine,
                           match_keys, partition_gain, quotient_link_loads)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache without the chip: keep them out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _v5e_256_tree():
    t = resolve("tpu_v5e-256").topology()
    return t.k, t.subtree.shape[0]


# kernel -> (function of the arguments, [(shape, dtype)] at caller widths)
CASES = {
    # device coarsening: one key per arc of a 2M-edge graph
    "match_keys": (
        lambda w, u, m: match_keys.match_keys_tiled(w, u, m),
        [((4 << 20,), jnp.float32)] * 3),
    # device initial partition: 1M vertices onto the 256-leaf tree
    "bucket_assign": (
        lambda c, b: bucket_assign.bucket_assign_tiled(c, b, k=256),
        [((1 << 20,), jnp.float32), ((255,), jnp.float32)]),
    # refinement connectivity rows: ELL 100k x 16, k = 64
    "partition_gain": (
        lambda b, w: partition_gain.partition_gain_ell(b, w, k=64),
        [((100_000, 16), jnp.int32), ((100_000, 16), jnp.float32)]),
    # the objective: 2M arcs onto the 256-leaf v5e tree
    "quotient_link_loads": (
        lambda bi, bj, w, s, f: quotient_link_loads.quotient_link_loads(
            bi, bj, w, s, f, k=_v5e_256_tree()[0]),
        [((2 << 20,), jnp.int32), ((2 << 20,), jnp.int32),
         ((2 << 20,), jnp.float32), (_v5e_256_tree()[::-1], jnp.float32),
         ((_v5e_256_tree()[1],), jnp.float32)]),
    # sharded-embedding lookup: 4096 bags of 50 over a 1M x 128 table
    "gather_combine": (
        lambda t, i, w: gather_combine.gather_combine(t, i, w),
        [((1 << 20, 128), jnp.float32), ((4096, 50), jnp.int32),
         ((4096, 50), jnp.float32)]),
    "bag_combine": (
        lambda g, w: bag_combine.bag_combine(g, w),
        [((4096, 50, 128), jnp.float32), ((4096, 50), jnp.float32)]),
    # qwen2-1.5b attention heads (12 query, 2 KV, head dim 128) at 4k
    "flash_attention": (
        lambda q, k, v: flash_attention.flash_attention_fwd(q, k, v),
        [((1, 4096, 12, 128), jnp.bfloat16),
         ((1, 4096, 2, 128), jnp.bfloat16),
         ((1, 4096, 2, 128), jnp.bfloat16)]),
    # GNN message passing: 20k nonzero 128 x 128 blocks
    "bsr_spmm": (
        lambda r, c, b, x: bsr_spmm.bsr_spmm(r, c, b, x, n_block_rows=1024),
        [((20_000,), jnp.int32), ((20_000,), jnp.int32),
         ((20_000, 128, 128), jnp.float32), ((1024 * 128, 128),
                                             jnp.float32)]),
}


def test_every_registered_kernel_has_a_compile_case():
    assert set(CASES) == set(KERNEL_REGISTRY)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = CASES[name]
    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mla_step_writes_its_latent_pool_in_place(one_chip):
    """The paged MLA step at DeepSeek-V2-Lite's widths (eight layers, 128
    slots of 88 pages of 16) keeps its donated latent pool row-major and
    writes it in place: its temporaries (one layer's gathered pages, 0.23
    GB, and the step's activations) stay below half the pool's 1.85 GB,
    where a copy of the pool would add all of it."""
    import dataclasses

    from repro import configs
    from repro.dist.sharding import lm_rules
    from repro.models import transformer as tr
    from repro.serving.engine import _jitted_decode
    full = configs.get("deepseek-v2-lite-16b").make_config("decode_32k")
    cfg = dataclasses.replace(full, n_layers=8, experts_held=(0, 8),
                              remat=False)
    rules = lm_rules(())
    shapes = jax.eval_shape(lambda k: tr.init(k, cfg, rules)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)
    slots, pages, page, width = 128, 88, 16, 640    # 512 + 64 padded
    pool = jax.ShapeDtypeStruct((8 * (slots * pages + 1), page, width),
                                cfg.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = _jitted_decode(cfg, rules).lower(
        params, pool, i32(slots, pages), i32(slots), i32(slots, 1)).compile()
    pool_bytes = 8 * (slots * pages + 1) * page * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2
    head = compiled.as_text().splitlines()[0]
    assert f"bf16[{8 * (slots * pages + 1)},{page},{width}]{{2,1,0" in head
