"""How the LFM2-MoE family's configuration file becomes the program's
model: the one place the benchmark names ``Lfm2MoeLM`` and
``Lfm2MoeConfig``. Found by the configuration's ``family``. The model
holds its parameters in the compute dtype, so the configuration's
``dtypes.params`` and ``dtypes.compute`` have to agree."""


def causal_lm(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLM

    dt = cfg["dtypes"]
    if dt["params"] != dt["compute"]:
        raise ValueError(
            f"the model holds its parameters in the compute dtype: "
            f"params {dt['params']} != compute {dt['compute']}")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "layer_types",
            "num_dense_layers", "num_experts", "num_experts_per_tok",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "norm_eps", "norm_topk_prob", "use_expert_bias",
            "routed_scaling_factor", "max_position_embeddings",
            "initializer_range")
    if cfg.get("conv_bias"):
        raise ValueError("conv_bias true is not implemented")
    return Lfm2MoeLM(
        Lfm2MoeConfig(rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                      head_dim=cfg.get("head_dim"),
                      **{k: cfg[k] for k in keys if k in cfg}),
        compute_dtype=jnp.dtype(dt["compute"]))
