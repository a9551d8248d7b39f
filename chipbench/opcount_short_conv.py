"""The least bytes the gated short convolutions of one step must move to
and from HBM, computed from shapes. Kept with the benchmark so that no
later PR can move the yardstick.

The operator between a short-convolution block's two projections reads the
three column blocks ``B``, ``C``, ``X`` of ``(tokens, hidden_size)`` float32
each and writes one array of that shape, ``C * conv(B * X)``; the
convolution is depthwise with ``conv_L_cache`` taps a channel, so a
position's neighbours are in the tile already and add no read. Whatever
computes it, in however few passes, a worker's turn cannot move less than,
for every position and channel (``E = tokens x hidden_size x 4`` bytes an
array):

* the first forward: ``B``, ``C``, ``X`` read, the result written, ``4 E``;
* the segment's second forward (its boundary is kept, the operator's
  result, which the output projection's weight gradient reads, is made
  again): the same, ``4 E``;
* the backward: ``B``, ``C``, ``X`` read, the result's cotangent read, the
  three blocks' cotangents written, ``7 E``;

``15 E``: 60 bytes a channel a position a block. The gated product ``B *
X``, the convolution's own result and anything a second pass reads again
are not counted, nor the taps and their gradient (``conv_L_cache x
hidden_size`` numbers): a share of the HBM peak from this count is a floor
of what the chip moved, and cannot pass 100.
"""

PASSES = {"first_forward": 4, "second_forward": 4, "backward": 7}  # in units of E


def conv_blocks(config: dict) -> int:
    """The blocks kept whose operator is the short convolution:
    ``layers_held`` (published layer numbers) read against the published
    ``layer_types``."""
    return sum(config["layer_types"][layer] == "conv" for layer in config["layers_held"])


def least_bytes_per_step(config: dict, mix: dict) -> float:
    """Over the configuration's short-convolution blocks and the honest
    workers, for the mix's tokens a worker."""
    honest = int(config["n_nodes"]) - int(config["n_byzantine"])
    array = float(mix["tokens_per_worker"]) * int(config["hidden_size"]) * 4.0
    return sum(PASSES.values()) * conv_blocks(config) * honest * array
