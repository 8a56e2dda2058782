"""A configuration file's numbers as the program, the reference and the counts
take them.

Configuration files use the published ``config.json`` keys of the model
(ChatGLM naming for GLM-4); this is the one place that maps them.
"""

from __future__ import annotations

from bench.counts import Dims

__all__ = ["layers_for", "dims", "ref_arch", "program_cfg"]


def layers_for(config: dict, traffic: dict) -> int:
    """Layers a cell runs: the file's ``num_layers`` per pipeline stage."""
    return int(config["num_layers"]) * max(int(traffic.get("pipeline_stages", 0)), 1)


def _numbers(config: dict, n_layers: int) -> dict:
    return dict(
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["multi_query_group_num"]),
        head_dim=int(config["kv_channels"]),
        d_ff=int(config["ffn_hidden_size"]),
        vocab=int(config["padded_vocab_size"]),
        n_layers=n_layers,
    )


def dims(config: dict, n_layers: int) -> Dims:
    return Dims(**_numbers(config, n_layers))


def ref_arch(config: dict, n_layers: int):
    from bench.reference.dense_gqa import Arch

    return Arch(
        **_numbers(config, n_layers),
        norm_eps=float(config["layernorm_epsilon"]),
        rope_theta=10000.0 * float(config["rope_ratio"]),
    )


def program_cfg(config: dict, n_layers: int):
    """The program's ``ArchConfig``: its registered config of this model with
    the file's numbers applied."""
    from repro.configs import get_config

    n = _numbers(config, n_layers)
    return get_config(config["program_arch"]).replace(
        name=config["name"],
        n_layers=n_layers,
        d_model=n["d_model"],
        n_heads=n["n_heads"],
        n_kv_heads=n["n_kv_heads"],
        head_dim=n["head_dim"],
        d_ff=n["d_ff"],
        vocab_size=n["vocab"],
        norm_eps=float(config["layernorm_epsilon"]),
        rope_theta=10000.0 * float(config["rope_ratio"]),
    )
