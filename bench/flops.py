"""Operations and bytes the model needs, from the configuration's shapes.

Copied from the program's `roofline.model.model_flops` (2 FLOPs per
weight per token, plus the attention term 4 * H * hd * context per
token per layer) and extended with bytes.  One departure: the
embedding table is a gather, not a matmul, so its weights count no
FLOPs and only the rows a step reads count bytes.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    weight_bytes: int = 2       # bytes per weight as served
    kv_bytes: int = 2           # bytes per KV element as served
    chips: int = 1

    @classmethod
    def of(cls, conf: dict) -> "Shapes":
        serve = conf["serve"]
        size = {"bfloat16": 2, "float32": 4}
        return cls(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                   heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   weight_bytes=size[serve["param_dtype"]],
                   kv_bytes=size[serve["cache_dtype"]],
                   chips=serve["mesh_shards"])

    @property
    def layer_weights(self) -> int:
        """Matmul weights of one layer (q, k, v, o; gate, up, down)."""
        hd = self.head_dim
        attn = self.d * hd * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * self.d * self.d_ff

    @property
    def matmul_weights(self) -> int:
        """Every matmul weight of a forward: the layers and the head."""
        return self.layers * self.layer_weights + self.d * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token over all layers (whole model)."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes

    def token_flops(self, context: int) -> float:
        """FLOPs of one token that attends `context` positions."""
        attn = 4.0 * self.heads * self.head_dim * context * self.layers
        return 2.0 * self.matmul_weights + attn

    def prompt_flops(self, start: int, n: int) -> float:
        """FLOPs of prompt positions [start, start + n): position p
        attends p + 1 positions."""
        ctx = n * start + n * (n + 1) / 2.0
        attn = 4.0 * self.heads * self.head_dim * ctx * self.layers
        return 2.0 * self.matmul_weights * n + attn

    def decode_least_bytes(self, lanes: int, distinct_kv_tokens: int) -> float:
        """Least HBM bytes one decode step must read, per chip: every
        layer and head weight once, one embedding row per lane, and the
        K/V of every distinct resident token a live lane attends, in
        whole pages counted by the caller."""
        w = self.matmul_weights * self.weight_bytes
        w += lanes * self.d * self.weight_bytes
        kv = distinct_kv_tokens * self.kv_bytes_per_token
        return (w + kv) / self.chips
