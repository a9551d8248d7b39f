"""Operations of the sliding-window attention kernels, computed from shapes.
Kept with the benchmark so that no later PR can move the yardstick.

A query at position ``i`` of a windowed block scores the keys ``j`` with ``0
<= i - j < W``: ``i + 1`` of them while ``i < W`` and ``W`` after, so a
head's score matrix has ``W (W + 1) / 2 + (T - W) W`` entries that matter for
``T > W`` (25,167,872 at 8192 / 4096, three quarters of the causal half) and
the causal ``T (T + 1) / 2`` for ``T <= W``. A product over them is ``2 x
head_dim`` operations an entry, and the kernels' kinds have the products of
their causal namesakes (``chipbench/opcount_attention_qk_v.PRODUCTS``: the
forward 2, dq 3, dk / dv 4), every one at the published head width (queries,
keys and values are all ``head_dim`` wide here). What a kernel multiplies
outside the window, or above the diagonal, inside the block pairs an edge
crosses is not counted: a share of the peak from these counts is a floor of
what the MXU did, and cannot pass 100.
"""

from chipbench import opcount_attention_qk_v

# a windowed kernel's name -> the causal kernel whose products it has
KINDS = {"window_attention_" + kind.rsplit("_", 1)[1]: kind
         for kind in opcount_attention_qk_v.PRODUCTS}


def window_entries(tokens: int, window: int) -> int:
    """The ``(i, j)`` with ``0 <= i - j < window`` among ``tokens``
    positions: one head's entries."""
    inside = min(tokens, window)
    return inside * (inside + 1) // 2 + (tokens - inside) * inside


def kernel_flops(kind: str, heads: int, head_dim: int, tokens: int, window: int) -> float:
    """One call of the windowed kernel ``kind`` on one sequence of ``tokens``."""
    products = len(opcount_attention_qk_v.PRODUCTS[KINDS[kind]])
    return float(heads) * window_entries(tokens, window) * 2.0 * head_dim * products
