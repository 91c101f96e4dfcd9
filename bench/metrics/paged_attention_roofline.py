"""Share of its roofline that the paged-attention decode kernel reaches:
the least time the chip could take for the attention the delivered
decode tokens need, over the kernel's summed device time in the trace.

The work is counted from shapes, whatever implements it.  One decode
row at context ``ctx`` (keys and values of ``ctx`` positions, the new
one included) in one layer needs
``ctx * KH * D * 2 (K and V) * kv_bytes`` bytes of cache, ``H * D *
2 * 2`` bytes of query and output (bf16), and ``4 * H * D * ctx`` flops
(``q.k`` and ``p.v``), ``ctx`` being the context that layer reads
(``Dims.layer_contexts`` of the cell's reference module: the whole
context in a full-attention layer).  A step's launch per layer takes at
least ``max(flops / peak_flops, bytes / hbm_bandwidth)``; the bound is
that, summed over layers and over the steps whose tokens reached the
host in the window.  A kernel that reads a page more than once, or
reads heads it does not use, reaches a lower share: it is not counted
differently.
Output token ``k >= 1`` of a request with a ``P``-token prompt came from
a decode at ``ctx = P + k``; token 0 came from prefill, not this kernel.
"""
from collections import Counter, defaultdict

from bench.metrics import device_trace, window_tokens

# what the kernel is called in the trace: its HLO instruction takes the
# name of the jitted wrapper in ``kernels/ops.py``
KERNEL = ("%_pa_jit",)


def row_work(ctx: int, dims, kv_bytes: int = 2, act_bytes: int = 2):
    """(flops, bytes) one decode row at context ``ctx`` needs in one
    layer."""
    H, KH, D = dims.n_heads, dims.n_kv_heads, dims.head_dim
    flops = 4 * H * D * ctx
    nbytes = ctx * KH * D * 2 * kv_bytes + 2 * H * D * act_bytes
    return flops, nbytes


def launch_bound_s(ctxs, dims, peaks, **kw) -> float:
    """Least seconds for one launch over rows at contexts ``ctxs``, in
    every paged-attention layer.  Layers that read the same contexts
    are counted once and multiplied."""
    layers = Counter(zip(*(dims.layer_contexts(c) for c in ctxs)))
    total = 0.0
    for layer_ctxs, n in layers.items():
        flops = nbytes = 0
        for c in layer_ctxs:
            f, b = row_work(c, dims, **kw)
            flops, nbytes = flops + f, nbytes + b
        total += n * max(flops / peaks["bf16_flops"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return total


def read(run):
    tr = device_trace(run)
    if tr is None:
        return None
    n, secs = tr.op_time(KERNEL)
    steps = defaultdict(list)        # tokens grouped by the step that
    for r, k in window_tokens(run):  # brought them to the host
        if k >= 1:
            steps[r.token_t[k]].append(len(r.prompt) + k)
    if not n or not steps or secs <= 0:
        return None
    bound = sum(launch_bound_s(c, run.dims, run.peaks)
                for c in steps.values())
    return 100.0 * bound / secs
