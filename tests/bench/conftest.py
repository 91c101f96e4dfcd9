"""Put the repository root (for ``bench``) and ``src`` on the path, and
give the tests a tiny cell that runs on the CPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False}


def tiny_cell(rate_rps=15.0, **check):
    """A cell at a size the CPU runs in seconds: internlm2's program
    config at tiny widths, chat-like traffic."""
    from bench import spec
    config = {"name": "tiny", "repro_config": "internlm2-20b",
              "reference": "dense_gqa", "model": dict(TINY_MODEL),
              "source": "test", "reduced": {}, "deployment": "test",
              "program": {"use_pallas": True}}
    mix = {"generator": "open_loop", "arrivals": {"process": "poisson"},
           "prompt_tokens": {"dist": "lognormal", "median": 20,
                             "sigma": 0.6, "min": 4, "max": 60},
           "output_tokens": {"dist": "lognormal", "median": 8,
                             "sigma": 0.5, "min": 2, "max": 16}}
    workload = {"config": "tiny", "traffic": "tiny", "chips": 1,
                "rate_rps": rate_rps, "lead_s": 0.5, "grace_s": 10.0,
                "engine": {"max_batch": 4, "max_len": 80, "block_size": 8,
                           "chunk_size": 16, "n_blocks": 40,
                           "compact_on_retire": False},
                "check": {"max_logit_gap": 0.01, "sample_tokens": 40,
                          "sample_requests": 4, **check},
                "why": "test"}
    e2e = [{"name": n, "unit": u} for n, u in
           (("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
            ("output_tok_s", "tokens/s"), ("setup_s", "s"))]
    return spec.Cell("tiny.test", workload, config, mix, e2e, [])


@pytest.fixture
def tiny():
    return tiny_cell()
