"""Compile rehearsals: every sparse-update kernel compiled for a described
TPU v5e at rwkv6-3b widths, with no chip attached.

Interpret mode (every other kernel test) never applies the chip's tiling
and memory rules; Mosaic does, here. Widths: 4,096 tokens per step
(batch 4 x seq 1024), d_model 2560, d_ff 8960, channel block 128, update
ratio 0.2 (4 of 20 blocks of a 2560-wide leaf, 14 of 70 of an 8960-wide
one), 2 trainable layers. Each test asserts the compiled HLO holds the
kernel as exactly one `tpu_custom_call`.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_dw import (batched_dw_kernel,
                                      batched_dw_pipelined_kernel)
from repro.kernels.fused_block_opt import fused_block_opt_kernel
from repro.kernels.masked_dw import (block_sparse_dw_kernel,
                                     block_sparse_dw_pipelined_kernel)
from repro.kernels.scatter_blocks import block_scatter_update_kernel
from repro.launch.hlo_analysis import kernel_launch_count

M, D, FF, BLOCK, STEPS = 4096, 2560, 8960, 128, 2
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: its entries for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(dev, fn, *shapes) -> str:
    sharding = SingleDeviceSharding(dev)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    # the default device steers Pallas's chip lookup (the pipelined kernels
    # size their DMA tiling by TPU generation) to the described chip
    with jax.default_device(dev):
        return jax.jit(fn).lower(*args).compile().as_text()


def _assert_one_kernel(text: str):
    assert "tpu_custom_call" in text
    assert kernel_launch_count(text) == 1


@pytest.mark.parametrize("pipelined", [False, True], ids=["grid", "pipelined"])
@pytest.mark.parametrize("k,n,n_sel", [(D, D, 4), (D, FF, 14), (FF, D, 4)],
                         ids=["time_mix", "chan_wk", "chan_wv"])
def test_masked_dw_compiles(v5e, k, n, n_sel, pipelined):
    kern = block_sparse_dw_pipelined_kernel if pipelined \
        else block_sparse_dw_kernel
    _assert_one_kernel(_compile(
        v5e, lambda x, dy, idx: kern(x, dy, idx, block=BLOCK),
        ((M, k), BF), ((M, n), BF), ((1, n_sel), I32)))


@pytest.mark.parametrize("pipelined", [False, True], ids=["grid", "pipelined"])
def test_batched_dw_compiles(v5e, pipelined):
    # the step's 4,096 tokens dispatched over 4 experts of capacity 1,024
    kern = batched_dw_pipelined_kernel if pipelined else batched_dw_kernel
    _assert_one_kernel(_compile(
        v5e, lambda x, dy, idx: kern(x, dy, idx, block=BLOCK),
        ((4, M // 4, D), BF), ((4, M // 4, D), BF), ((1, 4), I32)))


def test_scatter_blocks_compiles(v5e):
    _assert_one_kernel(_compile(
        v5e, lambda w, u, idx: block_scatter_update_kernel(w, u, idx),
        ((STEPS, D, D), BF), ((STEPS, D, 1, 4, BLOCK), BF),
        ((STEPS, 1, 4), I32)))


@pytest.mark.parametrize("kind", ["sgd", "momentum", "adamw"])
def test_fused_block_opt_compiles(v5e, kind):
    n_state = {"sgd": 0, "momentum": 1, "adamw": 2}[kind]

    def step(w, g, idx, *state):
        state = list(state) + [None] * (2 - n_state)
        return fused_block_opt_kernel(w, g, idx, 1e-3, 1.0, *state,
                                      kind=kind, momentum=0.9)

    _assert_one_kernel(_compile(
        v5e, step, ((STEPS, D, D), BF), ((STEPS, D, 1, 4, BLOCK), BF),
        ((STEPS, 1, 4), I32), *[((STEPS, D, D), F32)] * n_state))
