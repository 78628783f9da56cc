"""How the GPT-2 family's configuration file becomes the program's
model: the one place the benchmark names ``CausalLM`` and
``TransformerConfig``. Found by the configuration's ``family``; every
driver of the family builds its model here."""


def causal_lm(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.gpt import CausalLM
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    d = int(cfg["n_embd"])
    return CausalLM(TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), max_len=int(cfg["n_positions"]),
        d_model=d, n_layers=int(cfg["n_layer"]), n_heads=int(cfg["n_head"]),
        d_ff=int(cfg.get("n_inner") or 4 * d),
        # the program's one dropout (on the attention output) is drawn
        # in training only; a served model is in inference mode
        dropout=float(cfg["resid_pdrop"])
        if cfg["deployment"]["kind"] == "train" else 0.0,
        eps=float(cfg["layer_norm_epsilon"])),
        compute_dtype=jnp.dtype(cfg["dtypes"]["compute"]))
