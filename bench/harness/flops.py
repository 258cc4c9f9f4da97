"""Operations and bytes the algorithms need, from their shapes.

Counted as a matmul's 2*m*n*k; elementwise work, norms and softmax are
left out.  These are the yardstick for every utilization and roofline
share the benchmark reports, so they live with the benchmark and not in
the program.
"""
from __future__ import annotations

import math

# bytes per stored element of a paged KV plane, by the engine's kv_dtype
# ("f32" stores the model's own dtype; bf16 here), and the per-token,
# per-kv-head f32 scale the quantized planes carry beside each plane
KV_ELEM_BYTES = {"f32": 2, "bf16": 2, "int8": 1, "fp8": 1}
KV_SCALE_BYTES = {"f32": 0, "bf16": 0, "int8": 4, "fp8": 4}


# -- dense decoder LM (llama layout: MHA/GQA + SwiGLU) ------------------------


def lm_matmul_flops_per_token(m: dict) -> float:
    """One member, one token, every layer: the q/k/v/o projections and
    the SwiGLU MLP (no attention over the context, no LM head)."""
    d, hd = m["d_model"], m["head_dim"]
    H, Hkv, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    proj = 2 * d * hd * (2 * H + 2 * Hkv)
    mlp = 2 * 3 * d * ff
    return m["n_layers"] * (proj + mlp)


def lm_attn_flops(m: dict, ctx: float) -> float:
    """One member, one query token attending over `ctx` positions, every
    layer: scores (q.k) and the weighted sum of values."""
    return m["n_layers"] * 2 * 2 * m["n_heads"] * m["head_dim"] * ctx


def lm_head_flops(m: dict) -> float:
    return 2 * m["d_model"] * m["vocab_size"]


def decode_token_flops(m: dict, ctx: int) -> float:
    """All K members produce one token whose query sees `ctx` positions
    (itself included): projections, MLP, attention, LM head."""
    return m["members"] * (lm_matmul_flops_per_token(m)
                           + lm_attn_flops(m, ctx) + lm_head_flops(m))


def prefill_flops(m: dict, prompt_len: int) -> float:
    """All K members consume a prompt causally (position p sees p + 1
    positions) and score its last token (one LM head row)."""
    n = prompt_len
    ctx_sum = n * (n + 1) / 2
    return m["members"] * (n * lm_matmul_flops_per_token(m)
                           + lm_attn_flops(m, ctx_sum) + lm_head_flops(m))


def kv_bytes_per_token(m: dict, kv_dtype: str) -> float:
    """Paged-pool bytes one token occupies in one layer of one member:
    K and V planes plus their scales when quantized."""
    per_plane = (m["n_kv_heads"] * m["head_dim"] * KV_ELEM_BYTES[kv_dtype]
                 + m["n_kv_heads"] * KV_SCALE_BYTES[kv_dtype])
    return 2 * per_plane


def paged_attn_cost(m: dict, kv_dtype: str, ctx: int, page: int) -> tuple:
    """(flops, bytes) of the paged decode kernel for one query token of
    one member in one layer over a context of `ctx` live positions.  The
    kernel streams whole pages, so bytes count ceil(ctx / page) pages of
    K and V; the query and output rows are H*hd each in bf16."""
    pages = math.ceil(ctx / page)
    kv = pages * page * kv_bytes_per_token(m, kv_dtype)
    qo = 2 * m["n_heads"] * m["head_dim"] * 2
    flops = 2 * 2 * m["n_heads"] * m["head_dim"] * ctx
    return flops, kv + qo


# -- NiN (the paper's CIFAR network) ------------------------------------------

# (kind, out_channels, kernel, stride): the published NiN for 32x32 inputs
NIN_LAYERS = (
    ("conv", 192, 5, 1), ("conv", 160, 1, 1), ("conv", 96, 1, 1),
    ("maxpool", 0, 3, 2),
    ("conv", 192, 5, 1), ("conv", 192, 1, 1), ("conv", 192, 1, 1),
    ("avgpool", 0, 3, 2),
    ("conv", 192, 3, 1), ("conv", 192, 1, 1),
)


def _taps(hw: int, k: int) -> int:
    """Kernel taps that land inside a "SAME"-padded, stride-1 input of
    width hw, summed over the output positions of one dimension: the
    multiply-adds the padding's zeros do not need."""
    pad = (k - 1) // 2
    return sum(sum(1 for j in range(k) if 0 <= i + j - pad < hw)
               for i in range(hw))


def nin_forward_flops(img: int = 32, in_ch: int = 3,
                      n_classes: int = 100) -> float:
    """One image through NiN: each convolution's 2*cin*cout per
    multiply-add of an input pixel into an output pixel ("SAME" padding:
    taps on the padding are not counted), then the 1x1 classifier."""
    hw, ch, total = img, in_ch, 0.0
    for kind, out, k, s in NIN_LAYERS:
        if kind == "conv":
            total += 2 * ch * out * _taps(hw, k) ** 2
            ch = out
        else:
            hw = math.ceil(hw / s)
    total += 2 * ch * n_classes * hw * hw
    return total


def nin_train_flops(img: int = 32, in_ch: int = 3,
                    n_classes: int = 100) -> float:
    """Forward and backward of one image: the backward pass computes the
    gradients of inputs and of weights, two matmuls of the forward's
    size each, so 3x the forward in all."""
    return 3 * nin_forward_flops(img, in_ch, n_classes)
