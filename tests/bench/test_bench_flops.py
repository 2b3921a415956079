"""Each configuration's FLOPs per token and each kernel's FLOPs and bytes
against counts made by hand at smoke widths."""
import importlib.util
import os

import pytest

from bench.reference import dense_lm, moe_lm, rwkv6

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _kernel(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "bench", "kernels", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rwkv6_flops_per_token_by_hand():
    m = {"d_model": 64, "d_ff": 128, "vocab_size": 256, "num_layers": 3,
         "rwkv_head_dim": 16}
    got = rwkv6.flops_per_token(m, seq=32, k_train=2, ratio=0.2, block_req=16)
    # per layer: r,k,v,g,o 5*64*64 + decay lora 2*64*64 + channel mix
    # 64*128 + 128*64 + 64*64 = 49152 weights; wkv 4 heads * 4 * 16^2 = 4096
    # forward: 3 * (2*49152 + 4096) + head 2*64*256 = 339968
    # input grads: 2 layers * (2*49152 + 2*4096) - the first trainable
    # layer's r,k,v,g and lora-A reads of its input 2*(4*64*64 + 64*64)
    # + head 32768 = 204800
    # weight grads per layer: selected blocks (chan wk 2 of 8 blocks of 16:
    # 2*64*32; chan wr 1 of 4: 2*64*16; chan wv 2*128*16; five time-mix
    # 2*64*16) = 20480, lora A and B 2*2*64*64 = 16384; two layers: 73728
    assert got == {"forward": 339968, "backward": 204800 + 73728,
                   "total": 618496}


def test_dense_lm_flops_per_token_by_hand():
    m = {"d_model": 64, "d_ff": 128, "vocab_size": 256, "num_layers": 3,
         "num_heads": 8, "num_kv_heads": 2}
    got = dense_lm.flops_per_token(m, seq=32, k_train=2, ratio=0.2,
                                   block_req=16)
    # per layer: wq 64*64, wk/wv 2*64*16, wo 64*64, mlp 2*64*128 = 26624;
    # causal attention 2*2*(33/2)*64 = 4224 per token
    # forward: 3 * (2*26624 + 4224) + head 2*64*256 = 205184
    # input grads: 2 * (2*26624 + 2*4224) - 2*(64*64 + 2*64*16) + 32768
    # = 143872; weight grads: wq, wk, wv, wo one block of 16 each
    # (4 * 2*64*16), w_up 2 of 8 (2*64*32), w_down 2*128*16; * 2 layers
    assert got == {"forward": 205184, "backward": 143872 + 32768,
                   "total": 381824}


def test_moe_lm_flops_per_token_by_hand():
    m = {"d_model": 64, "d_ff": 32, "vocab_size": 256, "num_layers": 3,
         "num_heads": 4, "num_kv_heads": 2,
         "moe": {"num_experts": 8, "top_k": 2, "num_shared_experts": 2,
                 "layout": "all_but_first"}}
    got = moe_lm.flops_per_token(m, seq=32, k_train=2, ratio=0.2,
                                 block_req=16)
    # attention weights 2*64*64 + 2*64*32 = 12288, core 2*2*(33/2)*64 = 4224
    # dense layer: + 3*64*256 (width 8*32) = 61440; expert layer: + router
    # 64*8 + 2 routed and 2 shared SwiGLUs 4*3*64*32 = 37376
    # forward: (2*61440 + 4224) + 2*(2*37376 + 4224) + head 2*64*256
    # = 317824; input grads: 2*(2*37376 + 2*4224) - 2*(64*64 + 2*64*32)
    # + 32768 = 182784; weight grads per layer: router 2*64*8, wq/wk/wv/wo
    # one block of 16 each 4*2*64*16, routed w_gate/w_up 2 experts each
    # 2*(2*2*64*16), w_down 2*2*32*16, shared 3*2*64*16 = 25600; * 2 layers
    assert got == {"forward": 317824, "backward": 182784 + 51200,
                   "total": 551808}


@pytest.mark.parametrize("name,call,want", [
    ("masked_dw", {"m": 4096, "k": 2560, "cols": 512, "itemsize": 2},
     (2 * 4096 * 2560 * 512, (4096 * 2560 + 4096 * 512) * 2 + 2560 * 512 * 4)),
    ("batched_dw", {"e": 4, "c": 1024, "k": 2560, "cols": 512, "itemsize": 2},
     (2 * 4 * 1024 * 2560 * 512,
      4 * 1024 * (2560 + 512) * 2 + 4 * 2560 * 512 * 4)),
    ("fused_block_opt", {"rows": 5120, "cols": 512, "itemsize": 2,
                         "state": 2},
     (14 * 5120 * 512, 5120 * 512 * (3 * 2 + 16))),
    ("fused_block_opt", {"rows": 5120, "cols": 512, "itemsize": 2,
                         "state": 0},
     (2 * 5120 * 512, 5120 * 512 * 6)),
    ("scatter_blocks", {"rows": 100, "cols": 256, "itemsize": 2},
     (0, 2 * 100 * 256 * 2)),
])
def test_kernel_cost_by_hand(name, call, want):
    assert _kernel(name).cost(call) == want
