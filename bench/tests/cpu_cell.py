"""A llava-shaped cell at a tiny size for the CPU tests: the harness's
whole run (warm-up, window, metrics, reference check) without a chip."""
from __future__ import annotations

import copy
import os

from bench import registry, run

run._import_program()

TINY_CONFIG = {
    "name": "tiny-vlm",
    "source": "test",
    "arch": "llava-onevision-0.5b",
    "reference": "qwen2_vlm",
    "config": {"hidden_size": 128, "intermediate_size": 256,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "num_hidden_layers": 2, "vocab_size": 512,
               "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
               "tie_word_embeddings": True, "mm_hidden_size": 48},
    "overrides": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 2, "d_ff": 256, "vocab_size": 512,
                  "head_dim": 32, "vocab_pad_to": 64, "remat": False,
                  "vision_feat_dim": 48, "vision_tokens": 8,
                  "vision_token_buckets": [4, 8], "max_stage_batch": 2},
    "engine": {"n_slots": 4, "max_len": 256, "block_size": 16,
               "max_batch": 2},
}

TRAFFIC = {
    "open_poisson": {"kind": "open_poisson", "rate_rps": 6.0},
    "backlog": {"kind": "backlog", "depth_slots": 2, "max_requests": 5000},
    "closed": {"kind": "closed", "clients": 1, "think_s_mean": 0.05,
               "max_requests": 5000},
}
MIX = {"images": [{"bucket": 0, "share": 0.5}, {"bucket": 1, "share": 0.5}],
       "text_tokens": {"dist": "uniform", "min": 4, "max": 12},
       "output_tokens": {"dist": "uniform", "min": 3, "max": 6}}

CHECKS = {"requests": 4, "pad_positions": 64, "pad_outputs": 16,
          "limits": {"max_logit_gap": 0.05, "min_checked_requests": 2}}

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell(kind: str = "open_poisson", **traffic) -> registry.Cell:
    manifest = registry.load_manifest()
    t = {**TRAFFIC[kind], **copy.deepcopy(MIX), **traffic}
    return registry.Cell(
        name="tiny", entry={"name": "tiny", "chips": 1},
        config=copy.deepcopy(TINY_CONFIG), traffic=t,
        checks=copy.deepcopy(CHECKS),
        end_to_end=[m for m in manifest["end_to_end"]],
        per_layer=[m for m in manifest["per_layer"]],
        bench_dir=os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))))
