"""Share of its roofline that the expert layer's grouped matmul reaches:
the least time the chip could take for the expert work of the tokens
delivered in the window, over the kernel's summed device time in the
trace.

The work is counted from shapes and the window's tokens, whatever the
kernel does.  A launch of ``T`` tokens through one MoE layer sends
``T * top_k * n_held / n_experts`` (token, held expert) pairs to this
chip's experts on average, each needing ``6 * d * f`` flops (the gate,
up and down products of a SwiGLU expert of width ``f``); it reads the
bfloat16 weights of the held experts it is expected to touch,
``n_held * (1 - (1 - top_k / n_experts) ** T)`` of them at
``3 * d * f * 2`` bytes each, and its ``T`` tokens in and out
(``2 * T * d * 2`` bytes).  A launch takes at least ``max(flops /
peak_flops, bytes / hbm_bandwidth)`` per MoE layer.  The launches are
those that ``step_mfu_pct`` counts: one decode per step that brought
output tokens ``k >= 1`` to the host in the window (``T`` its rows), and
the prefill chunks of each prompt whose first token came in the window
(``chunk_size`` real tokens each, the rest in the last).  A kernel that
reads an expert twice, or computes pairs it holds no weights for,
reaches a lower share: it is not counted differently.
"""
from collections import Counter

from bench.metrics import device_trace, window_tokens

# what implements the work in the trace, by HLO instruction name: the
# jitted wrapper ``kernels/ops.py::_gmm_jit``, or the grouped-matmul
# kernels a TPU compiler lowers ``jax.lax.ragged_dot`` to
KERNEL = ("%_gmm_jit", "%ragged-dot")


def kernel_time(tr):
    """(count, device seconds) of the kernel's operations on device 0,
    matched on the instruction's own name (not on its operands)."""
    evs = [e for e in (tr.ops[0] if tr.ops else [])
           if e.name.split(" = ", 1)[0].startswith(KERNEL)]
    return len(evs), sum(e.dur for e in evs)


def launch_work(T: int, dims, w_bytes: int = 2, act_bytes: int = 2):
    """(flops, bytes) one launch of ``T`` tokens needs in one MoE
    layer."""
    d, f, K, E = dims.d_model, dims.d_ff, dims.top_k, dims.n_experts
    flops = 6 * d * f * T * K * dims.n_held / E
    touched = dims.n_held * (1.0 - (1.0 - K / E) ** T)
    nbytes = touched * 3 * d * f * w_bytes + 2 * T * d * act_bytes
    return flops, nbytes


def launch_bound_s(T: int, dims, peaks) -> float:
    """Least seconds for one launch of ``T`` tokens, over every layer."""
    flops, nbytes = launch_work(T, dims)
    return dims.n_layers * max(flops / peaks["bf16_flops"],
                               nbytes / peaks["hbm_bytes_per_s"])


def launches(run):
    """Tokens of each launch whose work reached the host in the window."""
    chunk = int(run.cell.engine["chunk_size"])
    decode = Counter()
    sizes = []
    for r, k in window_tokens(run):
        if k >= 1:
            decode[r.token_t[k]] += 1
        else:
            P = len(r.prompt)
            sizes += [chunk] * (P // chunk) + ([P % chunk] if P % chunk
                                               else [])
    return sizes + list(decode.values())


def read(run):
    tr = device_trace(run)
    if tr is None:
        return None
    n, secs = kernel_time(tr)
    sizes = launches(run)
    if not n or not sizes or secs <= 0:
        return None
    bound = sum(launch_bound_s(T, run.dims, run.peaks) for T in sizes)
    return 100.0 * bound / secs
