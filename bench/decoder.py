"""The pre-norm GQA decoder layout that Qwen3 and Llama share, as the
program takes it: its ModelConfig and its parameter leaves.  An
architecture file (`arch/<model_type>.py`) says which variant it is.
The reference forward of the same layout is `reference.decoder`."""
from __future__ import annotations


def program_config(conf: dict, *, qk_norm: bool):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    serve = conf["serve"]
    return ModelConfig(
        name=conf.get("name", conf["model_type"]), family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=qk_norm, rope_theta=float(conf["rope_theta"]),
        act=conf["hidden_act"], glu=True,
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=conf["tie_word_embeddings"],
        compute_dtype=serve["compute_dtype"],
        param_dtype=serve["param_dtype"])


def leaf_specs(conf: dict, *, qk_norm: bool) -> dict:
    """{path: (shape, kind, std)} of every parameter, kind "normal" or
    "norm" (a scale around 1)."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    h, kv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    ff, v = conf["intermediate_size"], conf["vocab_size"]
    specs = {
        "embed": ((v, d), "normal", 1.0),
        "layers/ln1/scale": ((L, d), "norm", 0.0),
        "layers/attn/wq": ((L, d, h * hd), "normal", d ** -0.5),
        "layers/attn/wk": ((L, d, kv * hd), "normal", d ** -0.5),
        "layers/attn/wv": ((L, d, kv * hd), "normal", d ** -0.5),
        "layers/attn/wo": ((L, h * hd, d), "normal", (h * hd) ** -0.5),
    }
    if qk_norm:
        specs["layers/attn/q_norm"] = ((L, hd), "norm", 0.0)
        specs["layers/attn/k_norm"] = ((L, hd), "norm", 0.0)
    specs.update({
        "layers/ln2/scale": ((L, d), "norm", 0.0),
        "layers/ffn/w_gate": ((L, d, ff), "normal", d ** -0.5),
        "layers/ffn/w_up": ((L, d, ff), "normal", d ** -0.5),
        "layers/ffn/w_down": ((L, ff, d), "normal", ff ** -0.5),
        "final_norm/scale": ((d,), "norm", 0.0),
    })
    if not conf["tie_word_embeddings"]:
        specs["head"] = ((d, v), "normal", d ** -0.5)
    return specs
