"""Qwen3 (hf:Qwen/Qwen3-8B, `model_type` "qwen3"): the pre-norm GQA
decoder with RMSNorm on each query and key head (QK-norm).

An architecture file gives the harness, found by the configuration's
`model_type`: `program_config` (the program's ModelConfig),
`leaf_specs` (the parameters the benchmark makes) and `forward` (the
plain float32 reference, which imports nothing of the program)."""
import decoder
import reference

QK_NORM = True


def program_config(conf):
    return decoder.program_config(conf, qk_norm=QK_NORM)


def leaf_specs(conf):
    return decoder.leaf_specs(conf, qk_norm=QK_NORM)


def forward(params, tokens, positions, conf):
    return reference.decoder(params, tokens, positions, conf,
                             qk_norm=QK_NORM)
