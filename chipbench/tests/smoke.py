"""Tiny cells for the CPU: the published configs' shapes cut to the
program's ``.smoke()`` widths, and short mixes, run through the same
loops as the chip's cells."""
from __future__ import annotations

import copy

from chipbench.common import Spec, program_config

SMOKE_HF = {
    "qwen3-0.6b": {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
                   "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1000000},
    "stablelm-3b": {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
                    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
                    "vocab_size": 256, "layer_norm_eps": 1e-5, "rope_theta": 10000,
                    "partial_rotary_factor": 0.25},
}


def serve_spec(arch: str = "qwen3-0.6b", *, slots: int = 4, max_len: int = 96,
               rate: float = 20.0, limit: float = 1.0) -> Spec:
    from repro.configs import get_arch

    mix = {"loop": "serve_open_loop", "arrivals": {"process": "poisson"},
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8, "max": 64,
                      "bucket": 16},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.8, "min": 2, "max": 24}}
    cell = {"slots": slots, "max_len": max_len, "rate_per_s": rate, "weights_dtype": "bfloat16",
            "limits": {"logit_gap": limit}}
    cfg = program_config(get_arch(arch).smoke(), cell)
    return Spec(name=f"{arch}.smoke", hf=copy.deepcopy(SMOKE_HF[arch]),
                bench={"arch": arch, "qk_norm": arch.startswith("qwen3")},
                mix=mix, cell=cell, cfg=cfg)


def train_spec(limits=None) -> Spec:
    from repro.configs import get_arch

    mix = {"loop": "train_steps", "seq_len": 32,
           "optimizer": {"lr": 3e-4, "warmup": 100, "total_steps": 10000, "floor": 0.1,
                         "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                         "clip_norm": 1.0}}
    cell = {"batch": 4, "weights_dtype": "float32",
            "limits": limits or {"loss_gap": 1e-2, "grad_gap": 0.1, "change_gap": 0.1}}
    cfg = program_config(get_arch("qwen3-0.6b").smoke(), cell)
    return Spec(name="qwen3-0.6b.smoke_train", hf=copy.deepcopy(SMOKE_HF["qwen3-0.6b"]),
                bench={"arch": "qwen3-0.6b", "qk_norm": True}, mix=mix, cell=cell, cfg=cfg)
