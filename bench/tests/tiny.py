"""A tiny cell for CPU tests: the doc4k shape at smoke widths, written
as files under a temporary checkout root so that ``spec.load`` finds it
the way it finds the real cells."""
import json
from pathlib import Path

from bench import spec

CONFIG = {"reference": "mistral", "family": "mistral",
          "model_type": "mistral", "hidden_size": 256,
          "num_attention_heads": 2, "num_key_value_heads": 2,
          "intermediate_size": 512, "vocab_size": 512,
          "num_hidden_layers": 2, "sliding_window": 64,
          "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
          "initializer_range": 0.02}
PRUNING = {"block": [128, 128], "rate": 0.6}
MIX = {"block_requests": 5, "prompt_lengths": [[64, 0.8], [128, 0.2]],
       "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
       "n_slots": 4, "seq_cap": 64, "warmup_s": 1, "drain_s": 30}


def cell(root, limit, pruned=True, rate=2.0):
    root = Path(root)
    for d in ("configs", "traffic", "workloads"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    cfg = dict(CONFIG, pruning=PRUNING) if pruned else CONFIG
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(MIX))
    (root / "bench/workloads/tiny.cell.json").write_text(json.dumps(
        {"rate_per_s": rate,
         "limits": {"served_gap_max": limit, "short_outputs": 0,
                    "unpacked_projections": 0}}))
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    b["workloads"] = [{"name": "tiny.cell", "config": "tiny",
                       "traffic": "tiny", "chips": 1}]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return spec.load("tiny.cell", root)
