"""Compile-only checks for a described TPU v5e: the six Pallas kernels,
and the decode step's KV pool.

Interpret mode (the parity tests) runs a kernel body on the CPU and says
nothing about whether the TPU compiler accepts its block shapes; these
tests lower every launcher with ``interpret=False`` at llama widths —
llama-2-7b's 32 heads of 128, 1024 keys, vocab 32000 padded to the vocab
tile the way ``kernels/ops.py`` pads it — for one chip of a described
``v5e:2x2`` topology, and require the Mosaic kernel (``tpu_custom_call``)
in the compiled HLO.  The TPU also picks each array's layout, which the
CPU does not: the decode step is compiled to show that its layer scan
updates the KV pool in the layout the TPU gives it, with no copy of the
pool.
Nothing runs: no chip is needed.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import attention as attn
from repro.kernels import dtv as dtv_k
from repro.kernels import verify as verify_k
from repro.launch import hlo_analysis
from repro.models import ModelConfig
from repro.models.model import LanguageModel

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


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_decode_step_updates_the_kv_pool_in_place(one_chip, layout):
    """Qwen1.5-4B's pool: 40 layers of 20 KV heads of 128 at 2 rows of
    1024 slots (narrow projections keep the compile quick).  The TPU lays
    such a pool out head-major (20 heads would pad to 24 in row-major
    tiles), and a scatter of whole (head, dim) rows, or a gather from the
    stacked pool, would make XLA copy the pool to row-major order and back
    around the layer scan."""
    cfg = ModelConfig(name="kv20", arch_type="dense", num_layers=40,
                      d_model=256, num_heads=20, num_kv_heads=20,
                      head_dim=128, d_ff=512, vocab_size=1024,
                      dtype=jnp.bfloat16)
    lm = LanguageModel(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0))[0]))
    state = on_chip(jax.eval_shape(
        lambda: lm.make_state(2, 1024, paged=layout == "paged")[0]))
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(lm.decode, donate_argnums=(1,)).lower(
        params, state, tokens).compile().as_text()
    # one layer's slice may be relaid out for its row gather; the whole
    # pool is never copied
    pool = {tuple(state.layers[name].shape) for name in ("k", "v")}
    assert hlo_analysis.copies_of(text, pool) == []
