"""Operations and bytes a decode step of an MLA + MoE decoder needs
(DeepSeek-V2 family), from the configuration's shapes (the keys of a
``configs/*.json`` file: hidden_size, num_hidden_layers,
num_attention_heads, q_lora_rank (null: a direct query projection),
kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
intermediate_size (the leading dense layers), first_k_dense_replace,
moe_intermediate_size, n_routed_experts (the experts held here),
routed_experts_published (the router's width), n_shared_experts,
vocab_size, tie_word_embeddings).

Decode runs attention in the absorbed form: a row's query is taken into
the latent space (q_nope . W_kv_b's key part), scored against each
cached latent and rotary key, and the weighted latents are taken back
out through W_kv_b's value part.  The cache holds kv_lora_rank +
qk_rope_head_dim values per position and layer.

Only useful work counts: the rows of a batch that hold a request, the
positions each attends to (its own length, not the cache's), and the
(token, expert) pairs that reach an expert held here, which the engine
counts (``LLMInstance.moe_held_pairs``).  Bytes: every stored weight
outside the routed experts once per step (the embedding table by the
rows it gives), each held expert that a pair reached once
(``LLMInstance.moe_experts_hit``), each row's cache to its length and
its new entry, its logits.  A multiply-add is two operations.  Weights,
cache and logits are bf16 (2 bytes); the router is float32.
"""
from __future__ import annotations

from typing import Sequence, Tuple

BF16, F32 = 2, 4


def _dims(cfg: dict):
    if cfg.get("q_lora_rank"):
        raise ValueError("counted for a direct query projection only")
    return (int(cfg["vocab_size"]), int(cfg["hidden_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
            int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))


def moe_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def attn_matmul_params(cfg: dict) -> int:
    """Weights one decoded token multiplies in one layer's attention: the
    query (d x H x (nope + rope)), the latent and rotary key
    (d x (r + rope)), the absorbed key and value parts of W_kv_b
    (H x nope x r, H x r x v) and the output (H x v x d)."""
    _, d, _, h, r, nope, rope, vd = _dims(cfg)
    return (d * h * (nope + rope) + d * (r + rope) + h * nope * r
            + h * r * vd + h * vd * d)


def expert_params(cfg: dict) -> int:
    """One routed expert's SwiGLU: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def _ffn_params(cfg: dict) -> Tuple[int, int, int]:
    """Per-token weights of the dense layer's SwiGLU, of the shared
    experts (one SwiGLU of their summed width) and of the router."""
    d = int(cfg["hidden_size"])
    dense = 3 * d * int(cfg["intermediate_size"])
    shared = 3 * d * int(cfg["n_shared_experts"]) * int(
        cfg["moe_intermediate_size"])
    router = d * int(cfg["routed_experts_published"])
    return dense, shared, router


def non_expert_bytes(cfg: dict) -> int:
    """Every stored weight but the routed experts and the embedding
    table: each layer's attention (W_kv_b whole) and norms (input, the
    latent's, post-attention), the dense layers' SwiGLU, each MoE
    layer's float32 router and shared experts, the final norm and the
    head."""
    v, d, n_layers, h, r, nope, rope, vd = _dims(cfg)
    dense, shared, router = _ffn_params(cfg)
    n_moe = moe_layers(cfg)
    attn = (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd)
            + h * vd * d + 2 * d + r)
    n = (n_layers * attn + (n_layers - n_moe) * dense + n_moe * shared
         + d + d * v)
    return BF16 * n + F32 * n_moe * router


def cache_bytes_per_token(cfg: dict) -> int:
    """Latent and rotary key of one position, over all layers."""
    _, _, n_layers, _, r, _, rope, _ = _dims(cfg)
    return BF16 * n_layers * (r + rope)


def decode_step(cfg: dict, contexts: Sequence[int], held_pairs: float,
                experts_hit: float) -> Tuple[float, float]:
    """(operations, bytes) of one batched decode step whose active rows
    attend to ``contexts`` positions each, in which ``held_pairs``
    (token, expert) pairs of those rows reached a held expert and
    ``experts_hit`` held experts were reached, both summed over the MoE
    layers."""
    v, d, n_layers, h, r, _, rope, _ = _dims(cfg)
    dense, shared, router = _ffn_params(cfg)
    n_moe = moe_layers(cfg)
    rows = len(contexts)
    per_row = 2 * (n_layers * attn_matmul_params(cfg)
                   + (n_layers - n_moe) * dense
                   + n_moe * (shared + router) + d * v)
    # scores against latent and rotary key, values over the latents
    attn = 2 * n_layers * h * (2 * r + rope) * sum(contexts)
    flops = rows * per_row + attn + 2 * expert_params(cfg) * held_pairs
    nbytes = (non_expert_bytes(cfg)
              + BF16 * expert_params(cfg) * experts_hit
              + cache_bytes_per_token(cfg) * (sum(contexts) + rows)
              + rows * BF16 * (d + v))
    return float(flops), float(nbytes)
