"""How the GLM-MoE-DSA family's configuration file becomes the program's
model: the one place the benchmark names ``GlmMoeDsaLM`` and
``GlmMoeDsaConfig``. Found by the configuration's ``family``. The model
holds its parameters in the compute dtype, so the configuration's
``dtypes.params`` and ``dtypes.compute`` have to agree. The file's
``num_experts`` and ``vocab_size`` are the share held here;
``n_routed_experts`` is the router's published width."""


def causal_lm(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                                       GlmMoeDsaLM)

    dt = cfg["dtypes"]
    if dt["params"] != dt["compute"]:
        raise ValueError(
            f"the model holds its parameters in the compute dtype: "
            f"params {dt['params']} != compute {dt['compute']}")
    if cfg.get("vocab_offset"):
        raise ValueError("a vocabulary slice is a smaller vocabulary: the "
                         "program takes ids of the slice, from 0")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "indexer_types",
            "mlp_layer_types", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "n_routed_experts", "num_experts", "expert_offset",
            "num_experts_per_tok", "n_shared_experts", "rms_norm_eps",
            "index_norm_eps", "norm_topk_prob", "routed_scaling_factor",
            "scoring_func", "topk_method", "n_group", "topk_group",
            "rope_interleave", "indexer_rope_interleave", "attention_bias",
            "num_nextn_predict_layers", "tie_word_embeddings",
            "max_position_embeddings", "initializer_range")
    return GlmMoeDsaLM(
        GlmMoeDsaConfig(rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                        **{k: cfg[k] for k in keys if k in cfg}),
        compute_dtype=jnp.dtype(dt["compute"]))
