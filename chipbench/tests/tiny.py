"""Tiny configurations and contexts for the harness's CPU tests."""
from __future__ import annotations

import copy
import os
import time

from chipbench import harness
from chipbench.compilelog import CompileLog

ROOT = harness.ROOT

TINY_LM = {"hidden_size": 256, "intermediate_size": 512,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 2, "vocab_size": 512,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "hidden_act": "silu", "tie_word_embeddings": False}


def serve_context(traffic_name="chat_decode", *, seed=7, seconds=2.0,
                  slots=4, max_len=64, control=False, model=None,
                  **traffic_over):
    conf = copy.deepcopy(harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", "yi-6b-serve.json")))
    conf["model"] = dict(model or TINY_LM)
    conf["engine"].update(num_slots=slots, max_len=max_len,
                          param_dtype="float32", fused_attention=False)
    conf["correctness"].update(sample_tokens=24, sample_requests=4)
    tr = copy.deepcopy(harness.load_json(os.path.join(
        ROOT, "chipbench", "traffic", traffic_name + ".json")))
    tr.update(rate_rps=2.0, warm_requests=2,
              prompt={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
              output={"median": 6, "sigma": 0.5, "min": 2, "max": 12})
    tr.update(traffic_over)
    cell = {"name": "tiny", "config": "tiny", "traffic": traffic_name,
            "chips": 1}
    return harness.Context(
        root=ROOT, cell=cell, config=conf, traffic=tr, seed=seed,
        seconds=seconds, tracer=harness.Tracer(False, 1),
        compiles=CompileLog(), t0=time.perf_counter(),
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
               "hbm_bytes": 1e9}, control=control)


TINY_VIT = {"hidden_size": 256, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 1024,
            "image_size": 32, "patch_size": 4, "num_channels": 3,
            "tokens": 65, "num_labels": 10, "hidden_act": "gelu",
            "rms_norm_eps": 1e-06, "param_dtype": "float32"}


def train_context(*, seed=5, seconds=2.0, chips=4, control=False,
                  **trainer_over):
    conf = copy.deepcopy(harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", "vit-1b-tp4.json")))
    conf["model"] = dict(TINY_VIT)
    conf["trainer"].update(batch=8, **trainer_over)
    tr = harness.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                        "train_images.json"))
    cell = {"name": "tiny-train", "config": "tiny", "traffic":
            "train_images", "chips": chips}
    return harness.Context(
        root=ROOT, cell=cell, config=conf, traffic=tr, seed=seed,
        seconds=seconds, tracer=harness.Tracer(False, chips),
        compiles=CompileLog(), t0=time.perf_counter(),
        device={"platform": "cpu", "kind": "cpu", "count": chips},
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
               "hbm_bytes": 1e9}, control=control)
