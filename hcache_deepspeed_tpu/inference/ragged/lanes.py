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

A step that holds decode lanes and a prompt slice is one program over
two such groups of lanes, ``[B_d, 1]`` and ``[B_s, T]``
(:func:`pack_step`: the groups' packed arrays, flattened, one after the
other: still one host array a dispatch). Inside it :class:`Lanes` says
how the rows of all lanes, which the matrix products take as one ``[1,
N, ...]`` array, are cut into the groups ``[B, T, ...]`` that the
per-lane operations (the K/V write, the attention kernels, a
recurrence) take one by one, each at its own shape.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.kv_write import flat_slots


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


def pack_step(groups) -> np.ndarray:
    """The lane groups of one step, each the arguments of
    :func:`pack_lanes`, in one flat host array."""
    return np.concatenate([pack_lanes(*group).ravel() for group in groups])


def unpack_step(flat, shapes, n_blocks: int, slot: bool = False):
    """The columns of every group (:func:`unpack_lanes`) of a flat
    ``flat`` whose groups have the static ``shapes`` ``((B, T), ...)``."""
    widths = [lanes_width(T, n_blocks, slot) for _, T in shapes]
    if flat.shape != (sum(B * w for (B, _), w in zip(shapes, widths)),):
        raise ValueError(f"an array of shape {flat.shape} is not lane "
                         f"groups of shapes {shapes}")
    groups, at = [], 0
    for (B, _), width in zip(shapes, widths):
        groups.append(unpack_lanes(
            flat[at:at + B * width].reshape(B, width), n_blocks, slot))
        at += B * width
    return groups


class LaneGroup:
    """``B`` lanes of ``T`` positions inside a forward: the columns of
    :func:`unpack_lanes` and what the trunk reckons from them once a
    program (``positions`` ``[B, T]``, ``kv_len`` ``[B]``, ``flat_idx``
    ``[B, T]``)."""

    __slots__ = ("tokens", "start", "tables", "t_len", "slots",
                 "positions", "kv_len", "flat_idx")

    def __init__(self, tokens, start, tables, t_len, slots=None):
        self.tokens, self.start, self.tables = tokens, start, tables
        self.t_len, self.slots = t_len, slots
        self.positions = self.kv_len = self.flat_idx = None

    @property
    def shape(self):
        """``(B, T)``."""
        return self.tokens.shape[:2]


class Lanes:
    """The lane groups of one forward. One group: the activations are
    ``[B, T, ...]`` and :meth:`rows`, :meth:`split` and :meth:`join`
    hand back what they are given, so such a program is the program it
    was before there were groups. Several: the activations are ``[1, N,
    ...]``, the groups' rows one after the other, cut and put together
    again around every per-lane operation."""

    def __init__(self, groups):
        self.groups = list(groups)
        #: of all rows, set by the trunk with the groups' own
        self.positions = self.flat_idx = None

    @classmethod
    def of(cls, columns, slot: bool = False):
        """From the flat columns of the groups, ``4 + slot`` each."""
        n = 4 + slot
        return cls(LaneGroup(*columns[i:i + n])
                   for i in range(0, len(columns), n))

    def over_tables(self, lo: int, hi: int):
        """These lanes over columns ``[lo, hi)`` of their tables alone:
        a trunk with two block pools carries each lane's two tables side
        by side, and every pool's writes and reads go by its own."""
        return Lanes(LaneGroup(g.tokens, g.start, g.tables[:, lo:hi],
                               g.t_len, g.slots) for g in self.groups)

    def place_positions(self):
        """Reckon every group's ``positions`` ``[B, T]`` (a lane's are
        consecutive from its ``start``) and those of all rows."""
        for g in self.groups:
            g.positions = g.start[:, None] + jnp.arange(g.shape[1])[None, :]
        self.positions = self.rows("positions")

    def place_slots(self, block_size: int, pool_slots: int):
        """Reckon every group's ``kv_len`` and the pool slots of its
        positions (``flat_idx``; padding gets ``pool_slots``), and the
        slots of all rows."""
        for g in self.groups:
            g.kv_len = g.start + g.t_len
            g.flat_idx = flat_slots(g.tables, g.start, g.t_len, g.shape[1],
                                    block_size, pool_slots)
        self.flat_idx = self.rows("flat_idx")

    def shared(self, fn, static=()):
        """``fn``, a per-lane operation, as a program of several groups
        calls it: jitted once a process (``static``: its static
        arguments' positions), so that a step program traces a kernel
        once for all its layers and not at all at a shape an earlier
        step program called it at (a third to a half of what building
        one costs the host, and set-up pays for each). One group: ``fn``
        itself, and the program's text stays what it was."""
        if len(self.groups) == 1:
            return fn
        # which side of an op runs is the platform's, not the shapes'
        from ...platform import get_platform
        return _jitted(fn, tuple(static), get_platform().supports_pallas())

    def rows(self, name):
        """The groups' ``[B, T]`` attribute ``name`` over all rows."""
        return self.join([getattr(g, name) for g in self.groups])

    def split(self, y, lead: int = 0):
        """``y`` ``[..., 1, N, ...]`` (rows at axes ``lead``, ``lead +
        1``) as each group's ``[..., B, T, ...]``."""
        if len(self.groups) == 1:
            return [y]
        parts, at = [], 0
        for group in self.groups:
            B, T = group.shape
            part = y[(slice(None),) * (lead + 1) + (slice(at, at + B * T),)]
            parts.append(part.reshape(*y.shape[:lead], B, T,
                                      *y.shape[lead + 2:]))
            at += B * T
        return parts

    def join(self, parts, lead: int = 0):
        """The inverse of :meth:`split`."""
        if len(parts) == 1:
            return parts[0]
        return _concatenate(
            [y.reshape(*y.shape[:lead], 1, -1, *y.shape[lead + 2:])
             for y in parts], lead + 1)

    def last_rows(self, x):
        """``x`` ``[.., ., H]`` at each lane's last valid position, the
        groups' lanes one after the other: ``[sum of B, H]``."""
        return _concatenate([jnp.take_along_axis(
            part, jnp.maximum(group.t_len - 1, 0)[:, None, None],
            axis=1)[:, 0] for group, part in
            zip(self.groups, self.split(x))], 0)


@functools.lru_cache(maxsize=None)
def _jitted(fn, static, kernels):
    del kernels             # a jit, and so a cache of traces, each
    return jax.jit(fn, static_argnums=static)


def _concatenate(parts, axis):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)
