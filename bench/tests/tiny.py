"""A cell at a size the CPU runs in seconds: the serving configuration's
layout and engine settings, at tiny widths and a short traffic mix."""
from __future__ import annotations

import copy

import harness

CONFIG = "stablelm-3b-s80"
TRAFFIC = "offline-batch"


def tiny_cell() -> harness.Cell:
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = copy.deepcopy(harness.load_json(harness.ROOT / conf["file"]))
    config["model"].update(num_hidden_layers=2, hidden_size=256,
                           num_attention_heads=4, num_key_value_heads=4,
                           head_dim=64, intermediate_size=512,
                           vocab_size=512)
    config["engine"].update(max_batch=4, max_len=256, n_pages=64)
    config["correct"].update(sample_requests=3)
    traffic = copy.deepcopy(harness.load_json(
        harness.BENCH / "traffic" / f"{TRAFFIC}.json"))
    traffic.update(requests=32)
    traffic["prompt_tokens"].update(median=24, min=8, max=64)
    traffic["output_tokens"].update(median=16, min=8, max=32)
    return harness.Cell("tiny", 1, config, traffic,
                        list(bench["end_to_end"]), list(bench["per_layer"]))
