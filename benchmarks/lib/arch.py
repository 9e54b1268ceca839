"""What a configuration file says about its stack, for the readers that turn
launches on the device trace into forward passes and expert traffic into
bytes: how many of its layers hold attention, how many hold experts, how wide
one expert is. Data and arithmetic only — no jax, nothing of the program.

A file whose layers are all alike says none of this (every layer has
attention; every layer has experts where `num_experts` > 0; an expert is
`intermediate_size` wide). A file whose layers differ says it with the
published keys: `layer_types` (one kind a layer; attention where the kind is
`full_attention`), `num_dense_layers` (leading layers with a dense MLP in a
sparse stack) and `moe_intermediate_size` (an expert's width where the dense
MLPs have another)."""

from __future__ import annotations

ATTENTION_KIND = "full_attention"
# the published spellings of "experts in a layer"; the first is the program's
EXPERT_COUNT_KEYS = ("num_experts", "num_local_experts", "n_routed_experts")


def num_experts(cfg: dict) -> int:
    return next((int(cfg[k]) for k in EXPERT_COUNT_KEYS if cfg.get(k)), 0)


def attention_layers(cfg: dict) -> int:
    kinds = cfg.get("layer_types")
    if kinds is None:
        return int(cfg["num_hidden_layers"])
    return sum(1 for kind in kinds if kind == ATTENTION_KIND)


def expert_layers(cfg: dict) -> int:
    if num_experts(cfg) <= 0:
        return 0
    return int(cfg["num_hidden_layers"]) - int(cfg.get("num_dense_layers", 0))


def expert_width(cfg: dict) -> int:
    return int(cfg.get("moe_intermediate_size", cfg["intermediate_size"]))
