"""Compiles for a described TPU v5e, without a chip: the Pallas kernels at the
widths the chip smoke runs them (the flash kernels at their chosen blocks,
at both benchmark cells' shapes), one qwen2-1.5b prefill cell on the
kernel path, the serving cell's qwen2-1.5b decode, and a one-lane decode
with the cache sharded over four chips.  Mosaic refuses here what
interpret mode accepts: block shapes off the (8, 128) tiling, more VMEM
than a kernel may use, primitives it cannot lower.  Nothing runs, so these
say nothing about results or times.

The topology is described inside a fixture: only one process may load the
TPU library, so describing it at import would break the other test workers.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import hw
from repro.configs.base import RunPolicy, ShapeSpec, get_config
from repro.core.benchscale import BENCH_SHAPES, bench_config
from repro.core.counters import measure_cell
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_kernel import rwkv6_wkv
from repro.launch.steps import build_cell
from repro.models import api
from repro.train.train_step import make_decode_step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, None, 0, None, None, False)


def _flash_bwd(q, k, v, w):
    def loss(q, k, v):
        return (_flash_fwd(q, k, v).astype(jnp.float32) * w).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


H, KVH, D, S, T = 12, 2, 128, 2048, 2048     # qwen2-1.5b heads, S, cache
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# internvl2-1b's training: batch 2, 14 query and 2 KV heads of 64
VB, VH, VD = 2, 14, 64

# each entry: the function, its argument shapes, and the names its Pallas
# kernels carry in the compiled module (the device trace's op names)
KERNELS = {
    "flash_fwd": (_flash_fwd, [((1, H, S, D), BF), ((1, KVH, S, D), BF),
                               ((1, KVH, S, D), BF)], {"flash_fwd"}),
    "flash_bwd": (_flash_bwd, [((1, H, S, D), BF), ((1, KVH, S, D), BF),
                               ((1, KVH, S, D), BF), ((1, H, S, D), BF)],
                  {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_fwd_internvl2": (_flash_fwd, [((VB, VH, S, VD), BF),
                                         ((VB, KVH, S, VD), BF),
                                         ((VB, KVH, S, VD), BF)],
                            {"flash_fwd"}),
    "flash_bwd_internvl2": (_flash_bwd, [((VB, VH, S, VD), BF),
                                         ((VB, KVH, S, VD), BF),
                                         ((VB, KVH, S, VD), BF),
                                         ((VB, VH, S, VD), BF)],
                            {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "flash_decode": (lambda *a: flash_decode(*a, interpret=False),
                     [((4, H, D), BF), ((4, KVH, T, D), BF),
                      ((4, KVH, T, D), BF), ((4, T), I32), ((4,), I32)],
                     {"decode_attn"}),
    # recurrentgemma-2b's RG-LRU width
    "rglru_scan": (lambda a, b: rglru_scan(a, b, interpret=False),
                   [((2, S, 2560), F32), ((2, S, 2560), F32)],
                   {"rglru_scan"}),
    # rwkv6-7b: 64 heads of 64
    "rwkv6_wkv": (lambda *a: rwkv6_wkv(*a, interpret=False),
                  [((1, 64, S, 64), BF)] * 4 + [((64, 64), BF)],
                  {"rwkv6_scan"}),
}
CUSTOM_CALL = re.compile(r"%([\w.-]+?)(?:\.\d+)? = .*custom_call_target="
                         r"\"tpu_custom_call\"")
# under grad the instruction is named for the transforms around the
# kernel too: transpose_jvp_flash_bwd_dq__
TRANSFORMS = re.compile(r"^(?:(?:transpose|jvp|vmap)_)+|_+$")


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(topo, name):
    fn, shapes, kernel_names = KERNELS[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    found = {TRANSFORMS.sub("", n)
             for n in CUSTOM_CALL.findall(compiled.as_text())}
    assert found == kernel_names


def test_qwen2_prefill_cell_compiles_with_kernels(topo):
    """The measurement path on one described chip: the prefill cell's
    compiled module calls the flash kernel (``tpu_custom_call`` in its
    text) and its memory_analysis() peak fits the chip."""
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    chip = hw.chip_of_meshes({"single": mesh})
    assert chip is hw.chip_spec("TPU v5 lite")
    cell = build_cell(get_config("qwen2-1.5b"),
                      ShapeSpec("prefill", "prefill", 2048, 1),
                      RunPolicy(use_pallas=True, remat="none",
                                params_f32=False), mesh)
    m = measure_cell(cell, chip)
    assert m.tpu_custom_calls > 0
    assert 0 < m.memory["peak_bytes"] < chip.hbm_bytes


def test_qwen2_decode_writes_each_layer_cache_in_place(topo):
    """The serving cell's decode (qwen2-1.5b, 64 lanes x 2688 positions) on
    one described chip, jitted with the state donated as ``ServingEngine``
    jits it: the whole state is aliased to the output, the layers are
    unrolled (no loop slicing each layer's cache out of a stack and writing
    it back), and the program accesses at most 1.5x the bytes of the
    weights plus one read of the cache."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = get_config("qwen2-1.5b")
    shape = ShapeSpec("decode", "decode", 2688, 64)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    def nbytes(tree):
        return sum(math.prod(s.shape) * s.dtype.itemsize
                   for s in jax.tree.leaves(tree))

    params = on_chip(api.abstract_params(cfg, BF))
    state = on_chip(api.state_specs(cfg, shape, BF)[0])
    batch = on_chip(api.input_specs(cfg, shape, BF)[0])
    step = make_decode_step(cfg, RunPolicy(use_pallas=True, remat="none"))
    compiled = jax.jit(step, donate_argnums=1).lower(
        params, state, batch).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes(state)
    assert not re.search(r"\bwhile\(", compiled.as_text())
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 1.5 * (nbytes(params) + nbytes(state))


def test_one_lane_decode_writes_the_cache_on_its_shards(topo):
    """A one-lane decode (qwen2-1.5b's search cell at 8192 positions) with
    each layer's cache sharded by sequence over four described chips: the
    row write stays on the shard that holds the row, so the program
    gathers nothing."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    cell = build_cell(bench_config("qwen2-1.5b"), BENCH_SHAPES["long_s"],
                      RunPolicy(remat="none", params_f32=False), mesh)
    assert jax.tree.leaves(cell.in_shardings[1])[0].spec[1] == "model"
    text = cell.lower().compile().as_text()
    assert not re.search(r" all-gather(?:-start)?\(", text)
