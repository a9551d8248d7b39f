"""Operations of the block-causal attention kernels, computed from shapes.
Kept with the benchmark so that no later PR can move the yardstick.

What counts is the causal half: a query at position t scores keys 0 .. t,
so a head's score matrix has ``T (T + 1) / 2`` entries that matter, counted
here as ``T^2 / 2``. A product over them is ``2 x head_dim`` operations an
entry. The kernels' kinds and their products a pair: the forward 2
(``q k^T`` and ``p v``), dq 3 (the scores again, ``do v^T``, ``ds k``),
dk/dv 4 (the scores again, ``p^T do``, ``do v^T``, ``ds^T q``). What a
kernel multiplies above the diagonal inside the blocks the diagonal crosses
is not counted: a share of the peak from these counts is a floor of what
the MXU did, and cannot pass 100.
"""

PRODUCTS = {"causal_attention_fwd": 2, "causal_attention_dq": 3, "causal_attention_dkv": 4}


def causal_product_flops(heads: int, head_dim: int, tokens: int) -> float:
    """One product over the causal half of every head's score matrix."""
    return heads * (tokens * tokens / 2.0) * head_dim * 2.0


def kernel_flops(kind: str, heads: int, head_dim: int, tokens: int) -> float:
    """One call of the kernel ``kind`` on one sequence of ``tokens``."""
    return PRODUCTS[kind] * causal_product_flops(heads, head_dim, tokens)
