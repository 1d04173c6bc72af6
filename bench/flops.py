"""Model FLOPs and the chip's peaks.

A family's ``step_flops`` (``bench/families/<family>.py``) counts the model
FLOPs of one train step by PaLM's convention (arXiv:2204.02311, App. B): 6
x the matmul parameters, the embedding table left out (it is a gather), x
the tokens, plus 12 * layers * heads * head_dim * seq per token for
attention.  Recomputation (remat) is not counted.  This module holds the
lookup of the peaks they are divided by."""


def peaks(device_kind: str, table: dict) -> dict:
    """The peak numbers of ``device_kind``; a kind not in the table is an
    error, never a default."""
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
