"""Helpers of the benchmark's CPU tests: a small spec root
(``BENCHMARK.json`` plus data files) whose cells run through the same
harness at a size the CPU holds."""
import json
import os
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: a Qwen2-shaped model small enough for the CPU
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128, vocab_size=512)


def tiny_config(base: str = "qwen2-1.5b", **over) -> dict:
    hf = json.loads((REPO / "bench" / "configs" / f"{base}.json").read_text())
    hf.update(TINY)
    if "head_dim" in hf:
        hf["head_dim"] = 16
    hf["program"]["use_flash"] = False
    hf.update(over)
    return hf


def make_root(root: Path, base: str = "qwen2-1.5b",
              logit_gap: float = 0.01) -> Path:
    """A spec root with cells ``tiny.longgen`` and ``tiny.code``: the
    repository's model and metric files, a tiny configuration and cells,
    and both mixes cut to the tiny ring."""
    root = Path(root)
    b = root / "bench"
    for d in ("configs", "cells", "traffic"):
        (b / d).mkdir(parents=True, exist_ok=True)
    for d in ("models", "metrics"):
        if not (b / d).exists():
            os.symlink(REPO / "bench" / d, b / d)
    (b / "configs" / "tiny.json").write_text(json.dumps(tiny_config(base)))
    cell = {"max_batch": 4, "s_max": 128, "block_size": 16,
            "prefill_chunk": 32, "prefill_token_budget": 64,
            "rate_per_s": 6.0, "check": {"logit_gap": logit_gap}}
    for name in ("tiny.longgen", "tiny.code"):
        (b / "cells" / f"{name}.json").write_text(json.dumps(cell))
    longgen = json.loads(
        (REPO / "bench" / "traffic" / "longgen.json").read_text())
    longgen["prompt"].update(median=40)
    longgen["output"].update(median=40)
    (b / "traffic" / "longgen.json").write_text(json.dumps(longgen))
    code = json.loads((REPO / "bench" / "traffic" / "code.json").read_text())
    code["prompt"].update(median=48, lo=32, hi=96)
    code["output"].update(median=6, lo=2, hi=16)
    (b / "traffic" / "code.json").write_text(json.dumps(code))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.longgen", "config": "tiny", "traffic": "longgen",
         "chips": 1, "why": "test"},
        {"name": "tiny.code", "config": "tiny", "traffic": "code",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({"tiny." + w.rsplit(".", 1)[1]
                                     for w in m["workloads"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root

