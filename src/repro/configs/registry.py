"""Registry of assigned architectures + reduced smoke variants."""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.configs.arch import ArchConfig, MLAConfig, MoEConfig, RGLRUConfig, XLSTMConfig

from repro.configs import (  # noqa: E402
    deepseek_v3_671b,
    qwen3_moe_30b_a3b,
    recurrentgemma_9b,
    gemma_2b,
    mistral_large_123b,
    internlm2_1_8b,
    stablelm_3b,
    musicgen_large,
    chameleon_34b,
    xlstm_1_3b,
)

ARCHS: Dict[str, ArchConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (
        deepseek_v3_671b,
        qwen3_moe_30b_a3b,
        recurrentgemma_9b,
        gemma_2b,
        mistral_large_123b,
        internlm2_1_8b,
        stablelm_3b,
        musicgen_large,
        chameleon_34b,
        xlstm_1_3b,
    )
}


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def smoke_config(name: str) -> ArchConfig:
    """A reduced config of the same family, runnable on CPU in seconds.

    Same block pattern / attention type / MoE-ness, tiny widths. The FULL
    configs are exercised only through the dry-run (ShapeDtypeStruct, no
    allocation).
    """
    cfg = get_arch(name)
    kw = dict(
        name=cfg.name + "-smoke",
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 4) if cfg.num_kv_heads > 1 else 1,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 96,
        dense_d_ff=96 if cfg.dense_d_ff else None,
        vocab_size=256,
        cross_seq=8,
    )
    # Keep the pattern but shrink the depth to ~one cycle + remainder.
    if cfg.moe is not None:
        kw["num_layers"] = 3 if cfg.moe_dense_first else 2
        kw["moe_dense_first"] = 1 if cfg.moe_dense_first else 0
        # grouped routing keeps its groups (4 of 4 experts, 2 picked) and,
        # as the published model does, drops nothing: every expert held,
        # computed dropless (a capacity at smoke size drops whole groups)
        grouped = cfg.moe.n_group > 1
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=16 if grouped else 8, top_k=2, d_expert=96,
            n_group=4 if grouped else 1, topk_group=2 if grouped else 1,
            num_experts_held=16 if grouped else None,
        )
    elif cfg.name.startswith("recurrentgemma"):
        kw["num_layers"] = 5  # (rglru, rglru, attn) + 2 remainder rglru
    elif cfg.name.startswith("xlstm"):
        kw["num_layers"] = 9  # one full 7:1 cycle + remainder
        kw["xlstm"] = dataclasses.replace(cfg.xlstm, num_heads=2, mlstm_chunk=8)
    else:
        kw["num_layers"] = 2
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                              qk_nope_head_dim=16, qk_rope_head_dim=8,
                              v_head_dim=16)
    if cfg.rglru is not None:
        kw["rglru"] = dataclasses.replace(cfg.rglru, lru_width=64)
    if cfg.local_window is not None:
        kw["local_window"] = 16
    return cfg.replace(**kw)
