"""A benchmark tree of CPU size for the tests: the real harness, generator,
reference and metric readers, with one tiny llama-family configuration
and one short traffic mix, written under a temporary directory."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY_CONFIG = {
    "source": "https://huggingface.co/01-ai/Yi-6B",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "vocab_size": 256, "reference": "llama",
    "serving": {"chips": 1, "max_lanes": 4, "kv_pool_bytes": 262144},
    "check": {"max_logit_gap": 0.04, "min_checked_tokens": 8},
}

TINY_MIX = {
    "kind": "open_loop",
    "rate_per_s": 12.0,
    "preroll_s": 0.5,
    "prefix": {"count": 2, "tokens": 16, "zipf_s": 1.0},
    "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "sampling": {"share": 0.25, "temperature": 0.8, "top_p": 0.95},
}


def make_tree(tmp: pathlib.Path, *, config=None, mix=None) -> pathlib.Path:
    """A checkout root under ``tmp`` holding BENCHMARK.json and bench/ with
    the cell ``tiny-chat`` (config ``tiny``, traffic ``tiny-mix``)."""
    root = tmp / "root"
    bench = root / "bench"
    for sub in ("lib", "generators", "references", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(config or TINY_CONFIG))
    (bench / "traffic" / "tiny-mix.json").write_text(
        json.dumps(mix or TINY_MIX))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": TINY_CONFIG["source"],
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU size"}]
    spec["workloads"] = [{"name": "tiny-chat", "config": "tiny",
                          "traffic": "tiny-mix", "chips": 1,
                          "why": "CPU size"}]
    for group in ("per_layer", "end_to_end"):
        for m in spec[group]:
            m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
