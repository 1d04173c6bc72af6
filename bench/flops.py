"""Model FLOPs of one train step, by PaLM's convention (arXiv:2204.02311,
App. B): 6 x the matmul parameters, the embedding table left out (it is a
gather), x the tokens, plus 12 * layers * heads * head_dim * seq per token
for attention.  Recomputation (remat) is not counted."""


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul: q, k, v, o, the gated FFN and the
    LM head of every layer; not the embedding gather, not the norms."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    ffn = 3 * d * cfg["intermediate_size"]
    head = d * cfg["vocab_size"]         # a matmul, tied to the table or not
    return cfg["num_hidden_layers"] * (attn + ffn) + head


def step_flops(cfg: dict, mix: dict) -> float:
    """Model FLOPs of one optimizer step over the mix's global batch."""
    tokens = mix["seq_len"] * mix["global_batch"]
    attn_per_token = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
                      * cfg["head_dim"] * mix["seq_len"])
    return float(6 * matmul_params(cfg) * tokens + attn_per_token * tokens)


def peaks(device_kind: str, table: dict) -> dict:
    """The peak numbers of ``device_kind``; a kind not in the table is an
    error, never a default."""
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
