"""Tiny stand-ins of the benchmark's configurations and mix, for CPU tests."""
import copy

from bench.dims import load_config
from bench.traffic import load_mix


def tiny_config(name: str, **over) -> dict:
    spec = copy.deepcopy(load_config(name))
    spec.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                vocab_size=256)
    spec["engine"] = {"n_slots": 4, "max_len": 256, "block_size": 16,
                      "pool_blocks": 0}
    spec["check"] = {"max_logit_gap": 0.004}
    spec.update(over)
    return spec


def tiny_mix(**over) -> dict:
    mix = copy.deepcopy(load_mix("azure_code"))
    mix.update(rate_per_s=6.0, warm_s=1.0, tail_s=2.0)
    mix["prompt"] = dict(mix["prompt"], sizes=4, min=8, max=96)
    mix["prompt"].update(median=40, mean=52)
    mix["output"] = dict(mix["output"], sizes=4, min=2, max=24)
    mix["output"].update(median=10, mean=13)
    mix.update(over)
    return mix
