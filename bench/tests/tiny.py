"""A tiny configuration and its cells, written as new files plus manifest
entries into a scratch copy of the benchmark's data: what a later change
adds to bring a cell, here at a size the CPU runs in seconds."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "arch": "smollm-135m", "reference": "dense",
    "source": "a reduced copy of smollm-135m for tests",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": True,
    "quant": {"bits": 4, "act_bits": 4, "act_group": None, "rank_frac": 0.1,
              "clip_ratio": 0.9},
    "deployment": {"slots": 4, "max_seq": 256, "page_size": 16,
                   "prefill_chunk": 64, "kv_dtype": "bf16"},
    "reduced": [],
}
MIXES = {
    "tiny-chat": {"loop": "open",
                  "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                             "min": 8, "max": 100},
                  "output": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                             "min": 2, "max": 8}},
    "tiny-rag": {"loop": "closed",
                 "prompt": {"dist": "uniform", "min": 60, "max": 100},
                 "output": {"dist": "uniform", "min": 2, "max": 6}},
}
CELLS = {
    "tiny.chat": {"rate_per_s": 3.0, "lead_s": 0.5, "check_requests": 3,
                  "limits": {"kv0_err": 1e-3, "gap_mean": 3.0}},
    "tiny.rag": {"per_client": 200, "lead_s": 0.5, "check_requests": 3,
                 "limits": {"kv0_err": 1e-3, "gap_mean": 3.0}},
}


def make_root(tmp: Path) -> Path:
    """A checkout-like root: the benchmark's data and manifest, plus the
    tiny configuration, its mixes and cells as new files and entries."""
    data = tmp / "bench"
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(BENCH / sub, data / sub)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    # the CPU has no peaks worth the name; these only let a CPU run finish
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 1e12,
                    "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
                    "source": "placeholder for CPU test runs"}
    (data / "peaks.json").write_text(json.dumps(peaks))
    (data / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    for name, mix in MIXES.items():
        (data / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, cell in CELLS.items():
        (data / "cells" / f"{name}.json").write_text(json.dumps(cell))
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny", "source": CONFIG["source"],
                                "file": "bench/configs/tiny.json",
                                "reduced": [], "why": "tests"})
    for name, traffic in (("tiny.chat", "tiny-chat"),
                          ("tiny.rag", "tiny-rag")):
        manifest["workloads"].append({"name": name, "config": "tiny",
                                      "traffic": traffic, "chips": 1,
                                      "why": "tests"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + ["tiny.chat", "tiny.rag"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp
