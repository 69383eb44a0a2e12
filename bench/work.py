"""Required work of a decoder-only transformer, counted from its shapes.

These functions count what the model needs, never what an implementation
moves: parameters are read once per decode tick, each active lane reads its
own live K/V and writes one position of it, and attention covers each lane's
real context. Padded lanes, inactive slots, gathered block tables and
recomputed tokens are not the model's work and are never passed in.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class ModelWork:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    weight_bytes: int = 2      # bf16 matrices
    norm_bytes: int = 4        # norm gains are kept in float32

    @classmethod
    def from_config(cls, c: dict) -> "ModelWork":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=c["tie_word_embeddings"])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def param_bytes(self) -> int:
        """Every stored parameter once: matrices, embedding (and head when
        untied), and the 2L+1 norm gains."""
        mats = self.layers * self.layer_matmul_params + self.head_params
        if not self.tied:
            mats += self.head_params
        norms = (2 * self.layers + 1) * self.d_model
        return mats * self.weight_bytes + norms * self.norm_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.weight_bytes

    def attn_flops(self, context: int) -> int:
        """QK^T and PV of one query position over ``context`` positions."""
        return 4 * self.layers * self.heads * self.head_dim * context

    def token_flops(self, context: int, with_head: bool) -> int:
        """One token through every layer attending ``context`` positions
        (itself included), plus the LM head when its logits are needed."""
        f = 2 * self.layers * self.layer_matmul_params + self.attn_flops(context)
        return f + (2 * self.head_params if with_head else 0)

    def decode_tick(self, contexts) -> tuple[int, int]:
        """(FLOPs, bytes) one decode tick requires. ``contexts`` lists, per
        lane that emits a token this tick, the positions it attends (its
        input token included)."""
        flops = sum(self.token_flops(c, True) for c in contexts)
        # c - 1 positions of live K/V are read and the input token's one
        # position is written
        nbytes = self.param_bytes + self.kv_bytes_per_token * sum(contexts)
        return flops, nbytes

    def prefill_flops(self, start: int, count: int, last: bool) -> int:
        """Prompt positions ``start .. start+count-1`` computed, each
        attending its own prefix; ``last`` adds the head for the position
        that seeds decoding."""
        n = count
        ctx_sum = n * start + n * (n + 1) // 2
        f = 2 * self.layers * self.layer_matmul_params * n \
            + self.attn_flops(1) * ctx_sum
        return f + (2 * self.head_params if last else 0)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device missing from the table is an
    error: a share of an unknown peak means nothing."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and which
    of the two bounds it."""
    tc = flops / peak["bf16_flop_s"]
    tm = nbytes / peak["hbm_byte_s"]
    return (tm, "memory") if tm >= tc else (tc, "compute")
