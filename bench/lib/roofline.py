"""Peaks of the chips the benchmark runs on, and the operations and bytes
of the work it counts, computed from shapes.

Peaks: Google Cloud documentation, "TPU v5e" (one chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device kind that is not
in the table is an error, never a default.

Model FLOPs (``mfu_pct``) count the work the requests asked for, not
what the program computed for padded or finished lanes.  A kernel's
operations and bytes count the work it was handed: every lane and step
of each call as the engine shaped it (finished lanes of a decode scan
included, padded lanes of a prefill group skipped as the kernel skips
them), at the keys each query may attend, so a share of its roofline
measures the kernel alone and the engine's waste shows elsewhere
(``decode_tokens_per_call``).  Bytes count each operand once (what a
kernel that reuses everything on chip would move), so a share of the
roofline never exceeds what the kernel could reach.
"""
from __future__ import annotations

from bench.lib.model import Dims

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}
F32 = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def layer_matmul_flops(d: Dims) -> int:
    """Projection and FFN FLOPs of one token through one layer."""
    qkv = 2 * d.d_model * (d.n_heads + 2 * d.n_kv_heads) * d.head_dim
    o = 2 * d.n_heads * d.head_dim * d.d_model
    ffn = 2 * 3 * d.d_model * d.d_ff
    return qkv + o + ffn


def attn_flops(d: Dims, keys: int) -> int:
    """QK^T and PV FLOPs of one query over ``keys`` keys, one layer."""
    return 4 * d.n_heads * d.head_dim * keys


def prefill_model_flops(d: Dims, prompt: int) -> int:
    """A whole prompt through the stack, causal (position i attends i+1
    keys), and one projection to the vocabulary for the first token."""
    keys = prompt * (prompt + 1) // 2
    return (d.n_layers * (prompt * layer_matmul_flops(d)
                          + attn_flops(d, 1) * keys)
            + 2 * d.d_model * d.vocab)


def decode_model_flops(d: Dims, context: int) -> int:
    """One decoded token whose query attends ``context`` keys."""
    return (d.n_layers * (layer_matmul_flops(d) + attn_flops(d, context))
            + 2 * d.d_model * d.vocab)


def prefill_call_cost(d: Dims, pos0, chunk) -> tuple[int, int]:
    """(ops, bytes) of ``paged_prefill_attention`` over one prefill call,
    all layers: each lane's ``chunk`` new positions after ``pos0`` cached
    ones, causal (position i attends pos0 + i + 1 keys); q in and out,
    the chunk's K/V in and written to pages, and every key read once."""
    ops = nbytes = 0
    for p, n in zip(pos0, chunk):
        p, n = int(p), int(n)
        if n <= 0:
            continue
        keys = n * p + n * (n + 1) // 2
        ops += attn_flops(d, 1) * keys
        nbytes += (2 * n * d.n_heads * d.head_dim
                   + 2 * (2 * n + p + n) * d.n_kv_heads * d.head_dim) * F32
    return d.n_layers * ops, d.n_layers * nbytes


def decode_call_cost(d: Dims, pos0, steps, n_steps: int) -> tuple[int,
                                                                   int]:
    """(ops, bytes) of ``paged_decode_attention`` over one decode scan of
    ``n_steps`` steps, all layers.  Every lane runs every step; a lane
    with a budget of ``k`` steps advances k times and then stays frozen
    (the kernel still attends its pos0 + k + 1 keys), so at step s it
    attends pos0 + min(s, k) + 1 keys.  q in and out, K/V read once."""
    n = int(n_steps)
    keys = 0
    for p, k in zip(pos0, steps):
        p, k = int(p), min(int(k), n)
        keys += n * (int(p) + 1) + k * (k - 1) // 2 + k * (n - k)
    ops = attn_flops(d, keys)
    qo = 2 * n * len(pos0) * d.n_heads * d.head_dim * F32
    kv = 2 * keys * d.n_kv_heads * d.head_dim * F32
    return d.n_layers * ops, d.n_layers * (qo + kv)


def roofline_seconds(ops: float, nbytes: float, pk: dict) -> tuple[float,
                                                                    str]:
    """The least time the chip could take, and which bound sets it."""
    tc, tb = ops / pk["flops"], nbytes / pk["hbm_bytes_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
