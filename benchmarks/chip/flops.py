"""Operations and bytes the algorithm needs, from the configuration's
shapes (the keys of a ``configs/*.json`` file: hidden_size,
num_hidden_layers, num_attention_heads, num_key_value_heads, head_dim,
intermediate_size, vocab_size, tie_word_embeddings).

Only useful work counts: the rows of a batch that hold a request, and
the keys each row attends to (its own length, not the cache's).  A
multiply-add is two operations.  Weights, the KV cache and the logits
are bf16 (2 bytes).

Decode row at context ``c``: the row attends to ``c`` keys (the prompt,
the tokens generated so far and the new one) and writes one new key and
value per layer.
"""
from __future__ import annotations

from typing import Sequence, Tuple

BF16 = 2


def _dims(cfg: dict):
    return (int(cfg["vocab_size"]), int(cfg["hidden_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
            int(cfg["intermediate_size"]))


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies in one layer (q, k, v, o, gate, up,
    down)."""
    _, d, _, h, kv, hd, f = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def param_bytes(cfg: dict) -> int:
    """Every stored weight, read once per step: the embedding (which is
    also the output head when tied), the layers with their norms, the
    final norm, and a separate head when untied."""
    v, d, n_layers, _, _, hd, _ = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d + 2 * hd
    n = v * d + n_layers * per_layer + d
    if not cfg.get("tie_word_embeddings", False):
        n += d * v
    return BF16 * n


def kv_bytes_per_token(cfg: dict) -> int:
    """Key and value of one position, over all layers."""
    _, _, n_layers, _, kv, hd, _ = _dims(cfg)
    return BF16 * n_layers * 2 * kv * hd


def decode_step(cfg: dict, contexts: Sequence[int]) -> Tuple[float, float]:
    """(operations, bytes) of one batched decode step whose active rows
    attend to ``contexts`` keys each."""
    v, d, n_layers, h, _, hd, _ = _dims(cfg)
    rows = len(contexts)
    per_row = 2 * (n_layers * layer_matmul_params(cfg) + d * v)
    attn = 4 * n_layers * h * hd * sum(contexts)
    flops = rows * per_row + attn
    kv = kv_bytes_per_token(cfg)
    # weights once; each row reads its keys and values and writes the
    # new ones, reads its embedding row and writes its logits
    nbytes = (param_bytes(cfg) + kv * (sum(contexts) + rows)
              + rows * BF16 * (d + v))
    return float(flops), float(nbytes)


def decode_attention(cfg: dict, contexts: Sequence[int]
                     ) -> Tuple[float, float]:
    """(operations, bytes) of one layer's decode-attention call over
    the active rows: q.k and p.v over each row's keys; q, k, v read and
    the output written."""
    _, _, _, h, kv, hd, _ = _dims(cfg)
    flops = 4 * h * hd * sum(contexts)
    nbytes = BF16 * (2 * kv * hd * sum(contexts) + 2 * h * hd * len(contexts))
    return float(flops), float(nbytes)


def prefill(cfg: dict, n: int) -> Tuple[float, float]:
    """(operations, bytes) of a batch-1 prefill of ``n`` tokens: the
    matmuls of every token, causal attention (n(n+1)/2 query-key
    pairs), the last position's logits; weights read once, the new keys
    and values written."""
    v, d, n_layers, h, _, hd, _ = _dims(cfg)
    flops = (2 * n * n_layers * layer_matmul_params(cfg)
             + 4 * n_layers * h * hd * n * (n + 1) // 2 + 2 * d * v)
    nbytes = (param_bytes(cfg) + kv_bytes_per_token(cfg) * n
              + BF16 * (n * d + v))
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's bound: the larger of operations over peak rate and
    bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
