"""How the EXAONE-MoE family's configuration file becomes the program's
model: the one place the benchmark names ``ExaoneMoeLM`` and
``ExaoneMoeConfig``. Found by the configuration's ``family``. The model
holds its parameters in the compute dtype, so the configuration's
``dtypes.params`` and ``dtypes.compute`` have to agree. The file's
``num_experts`` and ``vocab_size`` are the share held here;
``n_routed_experts`` is the router's published width;
``window_page_size`` (positions a page of a window layer's ring; the
model's default where the file has none) is the program's own."""


def causal_lm(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                      ExaoneMoeLM)

    dt = cfg["dtypes"]
    if dt["params"] != dt["compute"]:
        raise ValueError(
            f"the model holds its parameters in the compute dtype: "
            f"params {dt['params']} != compute {dt['compute']}")
    if cfg.get("vocab_offset"):
        raise ValueError("a vocabulary slice is a smaller vocabulary: the "
                         "program takes ids of the slice, from 0")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "layer_types",
            "mlp_layer_types", "sliding_window", "num_experts",
            "n_routed_experts", "expert_offset", "num_experts_per_tok",
            "num_shared_experts", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "norm_topk_prob", "routed_scaling_factor", "scoring_func",
            "n_group", "topk_group", "num_nextn_predict_layers",
            "tie_word_embeddings", "max_position_embeddings",
            "initializer_range", "window_page_size")
    return ExaoneMoeLM(
        ExaoneMoeConfig(rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                        **{k: cfg[k] for k in keys if k in cfg}),
        compute_dtype=jnp.dtype(dt["compute"]))
