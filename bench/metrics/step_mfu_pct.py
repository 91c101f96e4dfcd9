"""The model flops that the work delivered in the window needs, over
the traced window times chips times the chip's peak bf16 flop rate.

Per token through the trunk: the matmul flops of every layer, as the
cell's reference module counts them (``Dims.trunk_flops``; no embedding
gather, no norms).  The head (d_model x vocab) once per output token:
the prefill gives the first, each decode one more.  Attention:
``4 * H * D * c`` flops per token and paged-attention layer, ``c``
being the context that layer reads (``Dims.layer_contexts``), the
token itself counted.  A prompt counts whole (real tokens only, no
padding or overlap of chunks) when its first output token reached the
host in the window; output token ``k >= 1`` counts when it reached the
host in the window.
"""
from bench.metrics import device_trace, window_tokens


def attn_flops(dims, ctx: int) -> int:
    return 4 * dims.n_heads * dims.head_dim * sum(dims.layer_contexts(ctx))


def prefill_flops(dims, prompt: int) -> int:
    """The prompt through the trunk, causal attention, one head."""
    return (dims.trunk_flops() * prompt
            + sum(attn_flops(dims, p) for p in range(1, prompt + 1))
            + 2 * dims.d_model * dims.vocab)


def decode_flops(dims, ctx: int) -> int:
    """One decoded token at context ``ctx``."""
    return (dims.trunk_flops() + attn_flops(dims, ctx)
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
