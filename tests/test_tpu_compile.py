"""Compile-only checks of the six Pallas kernels for a described TPU v5e.

Interpret mode (the parity tests) runs a kernel body on the CPU and says
nothing about whether the TPU compiler accepts its block shapes; these
tests lower every launcher with ``interpret=False`` at llama widths —
llama-2-7b's 32 heads of 128, 1024 keys, vocab 32000 padded to the vocab
tile the way ``kernels/ops.py`` pads it — for one chip of a described
``v5e:2x2`` topology, and require the Mosaic kernel (``tpu_custom_call``)
in the compiled HLO.  Nothing runs: no chip is needed.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import attention as attn
from repro.kernels import dtv as dtv_k
from repro.kernels import verify as verify_k

B, H, HKV, D, S = 4, 32, 32, 128, 1024
T = 10                   # nodes of the 2x2x1 token tree
BS = 16                  # paged KV block size
V = 32768                # vocab 32000 padded to a multiple of BLK_V
R = verify_k.BLK_R


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache would store these compiles and fail to read them
    # back without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_cases():
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    kv = ((B, S, HKV, D), bf16)
    pool = ((B * S // BS, BS, HKV, D), bf16)
    return {
        "masked_decode_attention": (
            lambda q, k, v, m: attn.masked_decode_attention_pallas(
                q, k, v, m, interpret=False),
            [((B, H, D), bf16), kv, kv, ((B, S), jnp.bool_)]),
        "masked_tree_attention": (
            lambda q, k, v, m: attn.masked_tree_attention_pallas(
                q, k, v, m, interpret=False),
            [((B, T, H, D), bf16), kv, kv, ((B, T, S), jnp.bool_)]),
        "paged_flash_decode": (
            lambda q, k, v, t, m: attn.paged_flash_decode_pallas(
                q, k, v, t, m, interpret=False),
            [((B, T, H, D), bf16), pool, pool, ((B, S // BS), i32),
             ((B, T, S), jnp.bool_)]),
        "verify_stats": (
            lambda x, c: verify_k.verify_stats_pallas(x, c, interpret=False),
            [((R, V), f32), ((R,), i32)]),
        "topk": (
            lambda x: verify_k.topk_pallas(x, 2, interpret=False),
            [((R, V), f32)]),
        "dtv": (
            lambda a, b: dtv_k.dtv_pallas(a, b, interpret=False),
            [((R, V), f32), ((R, V), f32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
