"""A copy of the benchmark with one four-chip cell added as data files and
entries alone, as a PR that adds a cell adds it: a configuration file, a
mix, a limit, one ``workloads`` entry and the ``decode_collective_ms``
entry that lists it.  The model is Qwen2.5-shaped (RMSNorm, SwiGLU, QKV
biases, an untied head, GQA 8/4 so that the KV heads split over four
chips) at the sizes of ``tiny.py``, widened to 256 so that its heads fill
the model width as Qwen2.5's do."""
import copy as copy_lib
import json
import os
import shutil

import spec
import tiny

CONFIG, TRAFFIC = "qwen25-tiny", "conv_tiny"
CELL = f"{CONFIG}.{TRAFFIC}.4chip"
MODEL = {"n_layers": 2, "d_model": 256, "n_heads": 8, "n_kv_heads": 4,
         "head_dim": 32, "d_ff": 512, "vocab_size": 512,
         "rope_theta": 1000000.0, "norm_type": "rms", "norm_eps": 1e-05,
         "act_fn": "silu", "mlp_style": "gated", "qkv_bias": True,
         "tie_embeddings": False, "param_dtype": "float32"}
FILES = {
    f"configs/{CONFIG}.json": {
        "name": CONFIG, "source": "https://huggingface.co/Qwen/Qwen2.5-32B",
        "arch": "qwen2.5-32b", "reference": "dense", "reduced": [],
        "model": MODEL,
        "numerics": spec.config("deepseek-7b")["numerics"],
        "deployment": "a (data=1, model=4) mesh: every layer split over "
                      "four chips"},
    f"traffic/{TRAFFIC}.json": {
        "about": "Length-bucketed turns at a size the CPU serves.",
        "batch_size": 4, "max_seq": 256, "requests": 4, "bos_id": 257,
        "prompt_align": 32, "prompt_tokens": {"min": 64, "max": 64},
        "output_tokens": {"min": 20, "max": 60, "median": 30,
                          "sigma": 0.5}},
    f"limits/{CELL}.json": {"max_logit_gap": tiny.LIMIT,
                            "sample_requests": 3},
}
ENTRIES = {
    "configs": {"name": CONFIG, "source": "https://huggingface.co/Qwen/"
                "Qwen2.5-32B", "file": f"chipbench/configs/{CONFIG}.json",
                "reduced": [], "why": "GQA 8/4 with QKV biases, split over "
                "four chips"},
    "workloads": {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                  "chips": 4, "why": "the collectives of a tensor-parallel "
                  "decode step"},
    "per_layer": {"name": spec.MESH_ONLY, "unit": "ms", "better": "lower",
                  "source": "device_trace", "layer": "collectives",
                  "moves": "output_tok_s", "workloads": [CELL]},
}


def copy(root: str) -> None:
    """``root`` made to hold this checkout's ``BENCHMARK.json`` and
    ``chipbench/`` (its tests left out)."""
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(spec.HERE, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def add(root: str) -> dict:
    """Add the cell to the benchmark at ``root``: new files, and entries
    appended to ``BENCHMARK.json``'s lists.  Returns the new listing."""
    for rel, body in FILES.items():
        path = os.path.join(root, "chipbench", rel)
        if os.path.exists(path):
            raise FileExistsError(path)
        with open(path, "w") as f:
            json.dump(body, f, indent=2)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for key, entry in ENTRIES.items():
        bench[key].append(copy_lib.deepcopy(entry))
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
    return bench
