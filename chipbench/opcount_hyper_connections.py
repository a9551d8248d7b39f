"""The least bytes the hyper-connections of one step must move to and from
HBM, computed from shapes. Kept with the benchmark so that no later PR can
move the yardstick.

A position's residual is ``hc_mult`` streams of ``hidden_size`` float32
values, so a worker's streams are ``S = tokens x hc_mult x hidden_size x 4``
bytes. A hyper-connected sublayer (two a block: the mixer's and the
feed-forward's) reads the streams to make its mappings and its input and
writes the streams it hands on. Whatever is fused, it cannot move less than

* the first forward: the streams read once and written once, ``2 S``;
* the segment's second forward (its boundary is kept, the streams between
  its two sublayers are made again): read once, ``S``;
* the backward: the streams read, their cotangent read, the cotangent of
  the sublayer's input streams written, ``3 S``;

``6 S`` a sublayer a worker a step. The ``(tokens, hidden_size)`` arrays
(the sublayer's input and output and their cotangents), the mappings and
the connection's own parameters are not counted, nor a second read of the
streams by an op that is not fused with the first: a share of the HBM
peak from this count is a floor of what the chip moved, and cannot pass
100.
"""

PASSES = {"first_forward": 2, "second_forward": 1, "backward": 3}  # in units of S


def streams_bytes(tokens: int, hc_mult: int, hidden: int) -> float:
    """``S``: one worker's residual streams, float32."""
    return float(tokens) * hc_mult * hidden * 4.0


def least_bytes_per_step(config: dict, mix: dict) -> float:
    """Over the configuration's blocks (two sublayers each) and the honest
    workers, for the mix's tokens a worker."""
    honest = int(config["n_nodes"]) - int(config["n_byzantine"])
    sublayers = 2 * int(config["num_hidden_layers"])
    return (sum(PASSES.values()) * sublayers * honest * streams_bytes(
        int(mix["tokens_per_worker"]), int(config["hc_mult"]), int(config["hidden_size"])))
