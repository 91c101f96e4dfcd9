"""The model flops that the work delivered in the window needs, over
the traced window times chips times the chip's peak bf16 flop rate.

Per token through the trunk: 2 x the matmul parameters of every layer
(q, k, v, o projections and the gated MLP; no embedding gather, no
norms).  The head (d_model x vocab) once per output token: the prefill
gives the first, each decode one more.  Attention: ``4 * H * D * ctx``
flops per token and layer, ``ctx`` counting the token itself.  A prompt
counts whole (real tokens only, no padding or overlap of chunks) when
its first output token reached the host in the window; output token
``k >= 1`` counts when it reached the host in the window.
"""
from bench.metrics import device_trace, window_tokens


def matmul_params(dims) -> int:
    d, H, KH, D, f = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                      dims.head_dim, dims.d_ff)
    return dims.n_layers * (d * (H + 2 * KH) * D + H * D * d + 3 * d * f)


def attn_flops(dims, ctx: int) -> int:
    return 4 * dims.n_heads * dims.head_dim * ctx * dims.n_layers


def prefill_flops(dims, prompt: int) -> int:
    """The prompt through the trunk, causal attention, one head."""
    return (2 * matmul_params(dims) * prompt
            + attn_flops(dims, 1) * prompt * (prompt + 1) // 2
            + 2 * dims.d_model * dims.vocab)


def decode_flops(dims, ctx: int) -> int:
    """One decoded token at context ``ctx``."""
    return (2 * matmul_params(dims) + attn_flops(dims, ctx)
            + 2 * dims.d_model * dims.vocab)


def read(run):
    tr = device_trace(run)
    if tr is None:
        return None
    flops = 0
    for r, k in window_tokens(run):
        P = len(r.prompt)
        flops += prefill_flops(run.dims, P) if k == 0 \
            else decode_flops(run.dims, P + k)
    return 100.0 * flops / (tr.window_s * run.chips
                            * run.peaks["bf16_flops"])
