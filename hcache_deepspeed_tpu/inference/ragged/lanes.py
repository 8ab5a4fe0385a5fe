"""The lanes of one dispatch as one ``int32`` array.

A forward over ``B`` lanes of ``T`` positions is described by four small
integer arrays (five for a trunk with recurrent layers): the tokens, each
lane's first position, its count of valid positions, its block table and
its state slot. Handed to a jitted program one by one they are a
host-to-device transfer each, a quarter of a millisecond on the chip
whatever their size, and the device is idle meanwhile (PERF.md section
5). Packed they are one operand and one transfer::

    [B, T + 2 + n_blocks (+ 1)]:  tokens[:, :T] | start | t_len | tables | (slot)

:func:`pack_lanes` writes it on the host and :func:`unpack_lanes` cuts it
again inside the program, with static slices (``T`` is in the operand's
shape, ``n_blocks`` and the slot column in the model), so the trunk and
every kernel see the arrays they always saw.
"""

import numpy as np


def lanes_width(T: int, n_blocks: int, slot: bool = False) -> int:
    """Columns of the packed array of lanes of ``T`` positions."""
    return T + 2 + n_blocks + slot


def unpack_lanes(lanes, n_blocks: int, slot: bool = False):
    """``(tokens [B, T], start [B], tables [B, n_blocks], t_len [B])`` of
    a packed ``lanes``, then ``slots [B]`` when it has a ``slot`` column;
    in the order every forward takes them. Basic slices: views of a NumPy
    array, static slices of a traced one."""
    T = lanes.shape[1] - lanes_width(0, n_blocks, slot)
    if T < 1:
        raise ValueError(
            f"lanes of width {lanes.shape[1]} hold no token beside "
            f"{n_blocks} table entries")
    columns = (lanes[:, :T], lanes[:, T], lanes[:, T + 2:T + 2 + n_blocks],
               lanes[:, T + 1])
    return columns + ((lanes[:, T + 2 + n_blocks],) if slot else ())


def pack_lanes(tokens, start, tables, t_len, slots=None) -> np.ndarray:
    """The arrays of one dispatch in one host array, as
    :func:`unpack_lanes` cuts it (``slots``: a trunk with recurrent
    layers). Anything integer goes in: a column is cast as it is written.
    A few microseconds; the engine's buckets are at most 4 KB."""
    B, T = np.shape(tokens)
    n_blocks = np.shape(tables)[1]
    lanes = np.empty((B, lanes_width(T, n_blocks, slots is not None)),
                     np.int32)
    lanes[:, :T] = tokens
    lanes[:, T] = start
    lanes[:, T + 1] = t_len
    lanes[:, T + 2:T + 2 + n_blocks] = tables
    if slots is not None:
        lanes[:, T + 2 + n_blocks] = slots
    return lanes
