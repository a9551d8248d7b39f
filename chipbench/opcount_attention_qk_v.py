"""Operations of the block-causal attention kernels where a head's queries
and keys have one width and its values another, each product at its own
PUBLISHED width. Kept with the benchmark so that no later PR can move the
yardstick.

As ``chipbench/opcount_attention.py``: what counts is the causal half of a
head's score matrix, ``T^2 / 2`` entries, and a product over them is ``2 x
width`` operations an entry. Here the width is the product's own: one that
contracts over, or writes a gradient of, a query or a key is ``qk`` wide
(``qk_nope_head_dim + qk_rope_head_dim``), one with the values or the
output's cotangent ``v`` wide (``v_head_dim``). Columns the program pads
(queries and keys of 192 go to the kernels at 256) and what a kernel
multiplies above the diagonal inside the blocks the diagonal crosses are
not counted: a share of the peak from these counts is a floor of what the
MXU did, and cannot pass 100.
"""

# a kernel's products a pair, by the width each runs at
PRODUCTS = {
    "causal_attention_fwd": ("qk", "v"),               # q k^T, p v
    "causal_attention_dq": ("qk", "v", "qk"),          # the scores again, do v^T, ds k
    "causal_attention_dkv": ("qk", "v", "v", "qk"),    # the scores again, p^T do, do v^T, ds^T q
}


def kernel_flops(kind: str, heads: int, qk_dim: int, v_dim: int, tokens: int) -> float:
    """One call of the kernel ``kind`` on one sequence of ``tokens``."""
    width = {"qk": qk_dim, "v": v_dim}
    return heads * (tokens * tokens / 2.0) * 2.0 * sum(width[w] for w in PRODUCTS[kind])
