"""The work a configuration requires, whatever implements it.

Operations and bytes of a dense decoder-only transformer as functions of
its configuration file (``perfbench/configs/<config>.json``) and of the
live context: weights read once per decode tick at the configuration's
dtype, keys and values read over the live context only at the logical
head width, logits written once.  Not what a program happens to move (its
parameter dtype, a gather over the full context, lane padding): a program
that moves more reaches a smaller share of its roofline, never more than
all of it.
"""
from __future__ import annotations

from dataclasses import dataclass

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Counts:
    layer_weights: int        # matmul weights of one block
    small_weights: int        # norm scales and biases of every block
    head_weights: int         # output projection (tied or not)
    d_model: int
    vocab: int
    flops_per_key: int        # scores and weighted values, one key, one query
    kv_bytes_per_token: int   # keys and values of one token, every layer
    dtype_bytes: int
    n_layers: int

    @property
    def matmul_flops(self) -> int:
        """Multiply-adds (x2) of one token through every weight matrix,
        the output head included."""
        return 2 * (self.n_layers * self.layer_weights + self.head_weights)

    @property
    def weight_bytes(self) -> int:
        """Every weight a decode tick must read once."""
        return self.dtype_bytes * (self.n_layers * self.layer_weights
                                   + self.small_weights + self.head_weights)

    def decode_flops(self, events: int, contexts: int) -> int:
        """``events`` generated tokens that attend over ``contexts`` keys
        in all."""
        return events * self.matmul_flops + self.flops_per_key * contexts

    def prefill_flops(self, length: int) -> int:
        """A causal prompt of ``length`` tokens; logits at its last one."""
        return (length * (self.matmul_flops - 2 * self.head_weights)
                + 2 * self.head_weights
                + self.flops_per_key * length * (length + 1) // 2)

    def decode_bytes(self, ticks: int, events: int, contexts: int) -> int:
        """``ticks`` decode ticks that generate ``events`` tokens over
        ``contexts`` keys in all: weights once a tick, every key and value
        once, each token's embedding row and float32 logits."""
        return (ticks * self.weight_bytes + self.kv_bytes_per_token * contexts
                + events * (self.d_model * self.dtype_bytes + 4 * self.vocab))


def counts(cfg: dict) -> Counts:
    m = cfg["model"]
    L, d, F, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn = d * H * hd + 2 * d * Hkv * hd + H * hd * d
    gated = m["activation"] == "swiglu"
    mlp = (3 if gated else 2) * d * F
    norms = 2 if m["norm"] == "layernorm" else 1   # scale (+ bias)
    small = L * 2 * norms * d + norms * d
    if not gated:
        small += L * (F + d)                       # MLP biases
    if m.get("dual_head"):
        small += V                                 # per-token output bias
    return Counts(layer_weights=attn + mlp, small_weights=small,
                  head_weights=d * V, d_model=d, vocab=V,
                  flops_per_key=4 * H * hd,
                  kv_bytes_per_token=2 * L * Hkv * hd
                  * DTYPE_BYTES[m["dtype"]],
                  dtype_bytes=DTYPE_BYTES[m["dtype"]], n_layers=L)


def n_params(cfg: dict) -> int:
    """All parameters, the input embedding included (tied or not)."""
    c = counts(cfg)
    m = cfg["model"]
    embed = 0 if m.get("tie_embeddings") else c.d_model * c.vocab
    return c.n_layers * c.layer_weights + c.small_weights \
        + c.head_weights + embed
