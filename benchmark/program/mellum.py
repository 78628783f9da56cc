"""How the Mellum family's configuration file becomes the program's
model: the one place the benchmark names ``MellumLM`` and
``MellumConfig``. Found by the configuration's ``family``. The model
holds its parameters in the compute dtype, so the configuration's
``dtypes.params`` and ``dtypes.compute`` have to agree. Every expert
and every vocabulary row is held; ``window_page_size`` (positions a
page of a window layer's ring; the model's default where the file has
none) is the program's own."""


def causal_lm(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.mellum import MellumConfig, MellumLM

    dt = cfg["dtypes"]
    if dt["params"] != dt["compute"]:
        raise ValueError(
            f"the model holds its parameters in the compute dtype: "
            f"params {dt['params']} != compute {dt['compute']}")
    keys = ("vocab_size", "hidden_size", "moe_intermediate_size",
            "num_hidden_layers", "layer_types", "mlp_layer_types",
            "sliding_window", "num_experts", "num_experts_per_tok",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_parameters", "rms_norm_eps", "norm_topk_prob",
            "attention_bias", "use_sliding_window", "tie_word_embeddings",
            "max_position_embeddings", "initializer_range",
            "window_page_size")
    return MellumLM(MellumConfig(**{k: cfg[k] for k in keys if k in cfg}),
                    compute_dtype=jnp.dtype(dt["compute"]))
