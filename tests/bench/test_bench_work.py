"""The flop and byte counts the kernel roofline and step MFU divide by,
against hand arithmetic and against the program's parameter count."""
import dataclasses

import pytest

from bench import spec
from bench.metrics import paged_attention_roofline as pa
from bench.metrics import step_mfu_pct as mfu
from bench.references import dense_gqa
from bench.references.dense_gqa import Dims, matmul_params, model_dims

INTERNLM2 = Dims(n_layers=3, d_model=6144, n_heads=48, n_kv_heads=8,
                 head_dim=128, d_ff=16384, vocab=92544, rope_theta=1e6,
                 norm_eps=1e-5)
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_paged_attention_row_work_by_hand():
    flops, nbytes = pa.row_work(1000, INTERNLM2)
    assert flops == 4 * 48 * 128 * 1000 == 24_576_000
    # K and V of 1000 positions over 8 heads of 128 in bf16, plus the
    # bf16 query and output rows of 48 heads
    assert nbytes == 1000 * 8 * 128 * 2 * 2 + 2 * 48 * 128 * 2 == 4_120_576


def test_paged_attention_launch_bound_by_hand():
    ctxs = [1000, 3000]
    # 48/8 = 6 flops per KV byte: memory bound at 819 GB/s
    nbytes = 4_120_576 + (3000 * 8 * 128 * 4 + 2 * 48 * 128 * 2)
    want = 3 * nbytes / 819e9
    assert pa.launch_bound_s(ctxs, INTERNLM2, V5E) == pytest.approx(want)


@pytest.mark.parametrize("num_splits", [1, 4])
def test_work_is_the_same_however_the_kernel_splits(num_splits):
    """Counted per split of the context's pages and summed, the work is
    the work of the whole row: the count follows what attention needs,
    not how a launch divides it."""
    bs, ctx = 16, 1000
    pages = -(-ctx // bs)
    per = -(-pages // num_splits)
    spans = [(s * per * bs, min((s + 1) * per * bs, ctx))
             for s in range(num_splits)]
    kv = sum(pa.row_work(hi - lo, INTERNLM2)[1] - 2 * 48 * 128 * 2
             for lo, hi in spans if hi > lo)
    assert kv + 2 * 48 * 128 * 2 == pa.row_work(ctx, INTERNLM2)[1]
    assert sum(pa.row_work(hi - lo, INTERNLM2)[0] for lo, hi in spans
               if hi > lo) == pa.row_work(ctx, INTERNLM2)[0]


def test_mfu_matmul_params_match_count_params():
    """Matmul parameters: every parameter the program holds but the
    embedding table (a gather) and the RMSNorm weights."""
    from conftest import TINY_MODEL
    from repro.models.zoo import count_params

    from bench.program import model_cfg
    config = {"repro_config": "internlm2-20b", "model": TINY_MODEL}
    dims = model_dims(TINY_MODEL)
    total = count_params(model_cfg(config, dense_gqa))
    d, L = dims.d_model, dims.n_layers
    assert matmul_params(dims) + d * dims.vocab == \
        total - dims.vocab * d - (2 * L + 1) * d


def test_mfu_flops_by_hand():
    dims = dataclasses.replace(INTERNLM2, n_layers=1)
    per_layer = 6144 * (48 + 16) * 128 + 48 * 128 * 6144 + 3 * 6144 * 16384
    head = 2 * 6144 * 92544
    assert mfu.decode_flops(dims, 10) == \
        2 * per_layer + 4 * 48 * 128 * 10 + head
    assert mfu.prefill_flops(dims, 3) == \
        2 * per_layer * 3 + 4 * 48 * 128 * (1 + 2 + 3) + head


def test_unknown_device_kind_is_refused():
    with pytest.raises(spec.SpecError, match="not in bench/peaks.json"):
        spec.load_peaks("TPU v9 imaginary")
    assert spec.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
