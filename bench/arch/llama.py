"""Llama (`model_type` "llama", e.g. hf:deepseek-ai/deepseek-coder-33b-base):
the pre-norm GQA decoder without QK-norm.  The program has no RoPE
scaling, so a configuration that states `rope_scaling` is refused
rather than run as another model.  See qwen3.py for what an
architecture file gives the harness; the file must state `head_dim`."""
import decoder
import reference

QK_NORM = False


def program_config(conf):
    if conf.get("rope_scaling"):
        raise ValueError(f"{conf.get('name')}: rope_scaling "
                         f"{conf['rope_scaling']} is not in the program")
    return decoder.program_config(conf, qk_norm=QK_NORM)


def leaf_specs(conf):
    return decoder.leaf_specs(conf, qk_norm=QK_NORM)


def forward(params, tokens, positions, conf):
    return reference.decoder(params, tokens, positions, conf,
                             qk_norm=QK_NORM)
