"""Operations and bytes of the gated delta rule AS DEFINED, computed from
shapes. Kept with the benchmark so that no later PR can move the yardstick.

The rule, a position and a value head, from a state ``S (K x V)``:
``S <- exp(g) S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q``: three
products of ``K x V`` multiply-adds (``S^T k``, ``k u^T``, ``S^T q``), so
``6 K V`` operations a value head a position, whatever form computes them.
What it must move, a position: q and k (a key head's ``K`` each), v and o
(a value head's ``V`` each), g and beta (one number a value head each),
each array read or written once. The least time is the larger of the
operations over the matrix unit's peak and the bytes over the memory's
peak. A chunked form does more of both (the chunk's triangular system,
the quadratic part inside a chunk), so a share of this floor cannot pass
100.
"""


def flops_per_position(value_heads: int, key_dim: int, value_dim: int) -> float:
    """Three ``K x V`` products a value head."""
    return value_heads * 3 * 2.0 * key_dim * value_dim


def bytes_per_position(key_heads: int, value_heads: int, key_dim: int, value_dim: int,
                       itemsize: int = 4) -> float:
    """q, k, v, g, beta read once and o written once."""
    return itemsize * (2.0 * key_heads * key_dim + 2.0 * value_heads * value_dim
                       + 2.0 * value_heads)


def least_seconds_per_position(arch: dict, *, flops_per_s: float, bytes_per_s: float) -> float:
    hk, hv = int(arch["linear_num_key_heads"]), int(arch["linear_num_value_heads"])
    dk, dv = int(arch["linear_key_head_dim"]), int(arch["linear_value_head_dim"])
    return max(flops_per_position(hv, dk, dv) / flops_per_s,
               bytes_per_position(hk, hv, dk, dv) / bytes_per_s)


# a step runs the rule forward twice (the first forward and the segment's
# second) and backward once, the backward counted as two forwards
PASSES = 4.0


def least_seconds_per_step(config: dict, mix: dict, *, flops_per_s: float, bytes_per_s: float
                           ) -> float:
    """Every Gated DeltaNet block of the configuration, every honest
    worker's sequence, the step's passes."""
    layers, period = int(config["num_hidden_layers"]), int(config["full_attention_interval"])
    blocks = layers - layers // period
    honest = int(config["n_nodes"]) - int(config["n_byzantine"])
    return (least_seconds_per_position(config["reference"]["arch"], flops_per_s=flops_per_s,
                                       bytes_per_s=bytes_per_s)
            * int(mix["tokens_per_worker"]) * blocks * honest * PASSES)
