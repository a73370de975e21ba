"""Operations and bytes, computed from shapes.

Kernels: a Q8_0 kernel call reads activations x (M, K), int8 weights
q (N, K) and f32 block scales s (N, K/32), and writes f32 out (M, N); it
performs 2·M·K·N operations. The bytes are what the call must move at
least: each operand read once and the output written once. The shapes
are the ones the kernel is called with (the main K segment the offload
plan gives it, M and N padded as the backend pads them), read from the
custom call's HLO text in the device trace, so a call that a later change
reshapes is counted at its new shape.

Model: the FLOPs the Whisper forward needs, whatever runs it: a prefill
(encoder over the padded window, plus every decoder layer's cross-K/V
projection) and one decoded token at a given context (self-attention over
``ctx`` positions, cross-attention over the window, the MLP and the
vocabulary readout). Free decode slots and padding are not model work.

No number here comes from the offload ledger: it counts a scanned layer
stack once per program, not once per layer.
"""
from __future__ import annotations

import math
import re
from typing import List, Optional, Tuple

BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(BYTES) + r")\[([0-9,]*)\]")

Shape = Tuple[str, Tuple[int, ...]]


def _shapes(text: str) -> List[Shape]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def _nbytes(shape: Shape) -> int:
    return BYTES[shape[0]] * math.prod(shape[1])


def q8_work(m: int, k: int, n: int, x_bytes: int = 2,
            batch: int = 1) -> Tuple[int, int]:
    """(operations, bytes) of a Q8_0 kernel call of ``batch`` stacked
    (M, K) x (N, K) products sharing one x."""
    ops = 2 * batch * m * k * n
    nbytes = (m * k * x_bytes + batch * (n * k + n * (k // 32) * 4)
              + batch * m * n * 4)
    return ops, nbytes


def kernel_call(hlo: str) -> Optional[Tuple[int, int]]:
    """(operations, bytes) of one Q8_0 kernel call, from its HLO text in
    the trace (``%q8_matmul.3 = f32[M,N] custom-call(bf16[M,K] ..., s8[N,K]
    ..., f32[N,K/32] ...)``); None where the text names no such call."""
    if "custom-call(" not in hlo:
        return None
    head, _, args = hlo.partition("custom-call(")
    out = _shapes(head)
    ins = _shapes(args.split("custom_call_target")[0])
    if len(out) != 1 or len(ins) < 3:
        return None
    (_, odims), x, q = out[0], ins[0], ins[1]
    k = x[1][-1]
    ops = 2 * math.prod(odims) * k
    return ops, sum(_nbytes(s) for s in ins[:3]) + _nbytes(out[0])


def prefill_flops(cfg, frames: int) -> int:
    """Encoder over ``frames`` positions plus each decoder layer's
    cross-K/V projection of its output."""
    d, f = cfg.d_model, frames
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    front = 2 * f * cfg.n_mels * d
    layer = (2 * f * d * (qd + 2 * kvd) + 2 * f * qd * d     # q, k, v, o
             + 2 * 2 * f * f * qd                            # scores, PV
             + 2 * 2 * f * d * cfg.d_ff)                     # MLP
    cross = 2 * f * d * 2 * kvd
    return front + cfg.num_encoder_layers * layer + cfg.num_layers * cross


def token_flops(cfg, ctx: int, frames: int) -> int:
    """One decoded token whose self-attention sees ``ctx`` positions."""
    d = cfg.d_model
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    layer = (2 * d * (qd + 2 * kvd) + 2 * qd * d              # self q,k,v,o
             + 2 * 2 * ctx * qd                               # self attn
             + 2 * d * qd + 2 * qd * d                        # cross q, o
             + 2 * 2 * frames * qd                            # cross attn
             + 2 * 2 * d * cfg.d_ff)                          # MLP
    return cfg.num_layers * layer + 2 * d * cfg.vocab_size
