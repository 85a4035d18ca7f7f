"""The work of one forward of the Gomoku policy-value network, counted
from a configuration's `net` group and the board.

The network (`perfbench/reference/gomoku.py`, the same as the
program's `envs/policy_net.py`):

  input    the board seen from the player to move: 2 planes of 6 x 6
  trunk    `conv_layers` 3x3 convolutions, 'SAME', ReLU: 2 planes to
           `channels`, then `channels` to `channels`
  policy   a 1x1 convolution to 2 planes, a dense layer to 36 logits
  value    a dense layer to `value_hidden`, ReLU, a dense layer to 1, tanh

Operations: 2 for each multiply-add of the convolutions (every tap at
each of the 36 positions, the zero padding included, as a dense
convolution computes it) and of the dense layers.  The activations and
the input planes, a few operations an element, are left out.

Bytes: the least a microbatch moves through memory: each weight read
once, each row's board read (36 words) and its value and logits written
(37 words); activations stay on the chip.  4-byte words throughout
(the network runs in float32).
"""

from __future__ import annotations

WORD = 4
BOARD = 6
CELLS = BOARD * BOARD
IN_PLANES = 2
POLICY_PLANES = 2
KERNEL = 3


def _layers(net: dict) -> list[tuple[int, int]]:
    """(multiply-adds per row, weight words) of each layer."""
    c, h = net["channels"], net["value_hidden"]
    taps = KERNEL * KERNEL
    convs = [(IN_PLANES, c)] + [(c, c)] * (net["conv_layers"] - 1)
    out = [(CELLS * taps * i * o, taps * i * o) for i, o in convs]
    out.append((CELLS * c * POLICY_PLANES, c * POLICY_PLANES))
    for i, o in ((POLICY_PLANES * CELLS, CELLS), (c * CELLS, h), (h, 1)):
        out.append((i * o, i * o))
    return out


def weights(net: dict) -> int:
    """Weight words of the network."""
    return sum(w for _, w in _layers(net))


def ops_per_row(net: dict) -> int:
    return 2 * sum(m for m, _ in _layers(net))


def per_microbatch(net: dict, rows: int) -> dict:
    """Operations and bytes one forward of `rows` rows needs."""
    words = weights(net) + rows * (CELLS + 1 + CELLS)
    return {"ops": rows * ops_per_row(net), "bytes": words * WORD}
