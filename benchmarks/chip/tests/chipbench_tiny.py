"""A cell at a size the CPU test run can hold: the qwen2-7b program at 2
layers, width 128, 4 query heads and 1 KV head of 32, MLP 256 and a
vocabulary of 8192, served in bfloat16, under a small open-loop or
backlog mix. ``MODEL_TP4`` is its tensor-parallel form for 4 devices:
8 query heads and 4 KV heads of 16, so that each device holds 2 query
heads and 1 KV head, as a chip of the tp 4 cell holds 7 and 1."""
from __future__ import annotations

import dataclasses
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {"model_type": "qwen2", "hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 1, "intermediate_size": 256,
         "vocab_size": 8192, "num_hidden_layers": 2, "rope_theta": 1e6,
         "rms_norm_eps": 1e-6, "arch": "qwen2-7b-1chip", "chips": 1,
         "tp": 1,
         "runtime": {"paged": True, "page_size": 16, "admit_tail": 0,
                     "prefix_cache": False, "spec_decode": 0,
                     "decode_block": 16}}

MODEL_TP4 = dict(MODEL, num_attention_heads=8, num_key_value_heads=4,
                 arch="qwen2-7b", chips=4, tp=4)

# Served-token gap limit at this size: sound bf16 runs read 0 to 0.011 on
# five seeds on the CPU, the fp8 control 0.083 to 0.133.
TINY_LIMIT = 0.04


def program_cfg(dtype="bfloat16", model=MODEL):
    from repro.configs.base import get_config
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    return dataclasses.replace(
        get_config("qwen2-7b-1chip").reduced(), dtype=dtype,
        name="qwen2-7b-tiny", d_model=128, n_heads=h, n_kv_heads=hkv,
        head_dim=128 // h, d_ff=256, vocab=8192)


def cell(arrivals="stratified_poisson", model=MODEL):
    from benchmarks.chip import run_cell as RC
    mix = {"arrivals": arrivals, "rate_per_s": 20.0, "stratum_s": 0.5,
           "backlog_requests": 400, "block_requests": 16,
           "prompt_tokens": [17, 32], "output_tokens": [4, 24],
           "ramp_s": 0.5, "drain_cap_s": 20, "check_requests": 3}
    if arrivals == "stratified_poisson":
        e2e = [{"name": "ttft_p90_s", "unit": "s"},
               {"name": "tpot_p90_ms", "unit": "ms"},
               {"name": "setup_s", "unit": "s"}]
    else:
        e2e = [{"name": "output_tokens_per_s", "unit": "tokens/s"},
               {"name": "setup_s", "unit": "s"}]
    per_layer = [{"name": "queue_wait_p90_s.chat", "unit": "s"},
                 {"name": "device_idle_share.chat", "unit": "%"},
                 {"name": "batch_occupancy.batch", "unit": "%"}]
    return RC.Cell("tiny", dict(model), mix,
                   {"max_batch": 4, "reference_rows": 2,
                    "limits": {"served_logit_gap": TINY_LIMIT}},
                   e2e, per_layer, RC.load_family(ROOT, model))
