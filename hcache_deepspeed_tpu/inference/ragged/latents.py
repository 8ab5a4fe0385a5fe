"""Host storage for HCache latent payloads, and the road there.

A preempted-to-latents sequence accumulates one ``[L, t, H]`` latent
chunk per forward (prefill once, then one token per decode step). The
naive accumulation — ``np.concatenate`` per step — reallocates and
copies the whole history on every decoded token (O(T^2) bytes copied
over a generation) and leaves the payload wherever the last concat put
it. :class:`HostLatentStore` keeps ONE layer-major (C-contiguous
``[L, capacity, H]``) host buffer, sized once from the capacity its
owner gives (``len(prompt) + max_new_tokens``; amortized doubling along
the token axis is the fallback for owners that give none), so:

* absorbing a decode step is an O(L*H) copy into place;
* the restore payload is a zero-copy view whose per-layer-chunk slices
  ``[l0:l0+C, :T]`` walk memory in layer-major order — the same order
  the restore pipeline ships them host→device, so staging a chunk is a
  straight block copy instead of a gather;
* the dtype is whatever the engine captured (``hcache.latent_dtype``,
  e.g. ``float8_e4m3fn`` to halve the wire/storage bytes) — the store
  never up-casts.

**Deferred landing.** The serving engine does not wait for a
dispatch's latents: :class:`LatentProgram` starts their copy to the
host when the program is enqueued, and ``put`` hands out
:class:`PendingLatents` — array-likes that name the lanes of such
programs. ``HostLatentStore.append`` records a pending chunk without
copying (``len``, ``shape`` and ``nbytes`` count it at once); the
engine copies it into place piece by piece while a later program runs
(:meth:`HostLatentStore.land`), and a reader that cannot wait
(``view()``, ``np.asarray``) lands what is left itself, inside a
``serve.latents.force`` span. Ndarray chunks land on ``append`` as
before: one store, two kinds of input.
"""

import contextlib
import math
import time
import weakref
from collections import deque
from typing import List, Optional, Tuple

import numpy as np

from ...telemetry.tracer import get_tracer

#: a landing copies at most this much between two looks at the program
#: in flight: a quarter of one layer of a 512-token chunk at 7B widths,
#: about a millisecond into pages never touched before
LAND_PIECE_BYTES = 1 << 20


class HostLink:
    """When a program's latents will have reached the host.

    jax starts a device-to-host copy (``copy_to_host_async``) but has no
    way to ask whether it has arrived: ``np.asarray`` waits. A landing
    that waited would stall the serving loop for the rest of the copy,
    so the engine asks this model first and leaves a program alone
    until it is due. The model is one serial link with a cost per byte,
    learned from the copies that *were* waited for (such a wait is an
    exact reading) and eased by a twentieth whenever a copy of some
    size had already arrived when asked for (which gives a bound
    only). It also keeps the ledger :meth:`InferenceEngineV2.latent_stats`
    reads."""

    #: a wait shorter than this is no reading of the link
    WAITED_S = 1e-3
    EASE = 0.95

    def __init__(self):
        self.seconds_per_byte = 0.0     # learned from the first wait
        #: wall clock less ``_lag`` is the link's own time: a copy that
        #: came later than modelled moves the copies behind it as well
        self._lag = 0.0
        self._free_at = 0.0             # link time
        self.captured_bytes = 0
        self.captured_tokens = 0        # tokens of the lanes handed out
        self.landed_hidden_bytes = 0
        self.landed_forced_bytes = 0

    def enqueue(self, program, now: float) -> None:
        """``program`` finished on the device at ``now``: its copy
        follows the copies queued before it."""
        program.start_at = max(self._free_at, now - self._lag)
        program.due_at = self._free_at = program.start_at + \
            program.nbytes * self.seconds_per_byte

    def due(self, program, now: float) -> bool:
        return program.due_at is not None and \
            now - self._lag >= program.due_at

    def arrived(self, program, asked_at: float, got_at: float) -> None:
        if program.due_at is None or program.nbytes < LAND_PIECE_BYTES:
            return      # never queued, or too small to read the link by
        if got_at - asked_at >= self.WAITED_S:
            got_at -= self._lag
            self.seconds_per_byte = max(
                got_at - program.start_at, 0.0) / program.nbytes
            self._lag += got_at - program.due_at
        else:
            self.seconds_per_byte *= self.EASE


class LatentProgram:
    """One dispatch's latents ``[L, B, T, H]`` on their way to the host.
    The copy starts here; :meth:`host` waits for it (once) and lets the
    device buffer go."""

    __slots__ = ("shape", "dtype", "nbytes", "link", "_device", "_host",
                 "start_at", "due_at")

    def __init__(self, array, link: HostLink):
        self.shape = tuple(array.shape)
        self.dtype = np.dtype(array.dtype)
        self.nbytes = math.prod(self.shape) * self.dtype.itemsize
        self.link = link
        self._device = array
        self._host = None
        self.start_at = self.due_at = None
        array.copy_to_host_async()

    @property
    def device_bytes(self) -> int:
        """Device memory this program's latents still hold."""
        return self.nbytes if self._device is not None else 0

    def chunk(self, lane: int, n: int) -> "PendingLatents":
        """The first ``n`` tokens of ``lane``, as a pending chunk."""
        return PendingLatents([_Part(self, lane, n)])

    def host(self) -> np.ndarray:
        if self._host is None:
            asked_at = time.perf_counter()
            self._host = np.asarray(self._device)
            self.link.arrived(self, asked_at, time.perf_counter())
            self._device = None
        return self._host


class _Part:
    """``n`` tokens of one lane of one program; once a store has
    adopted it, where they go and how many layers are there."""

    __slots__ = ("program", "lane", "n", "nbytes", "store", "offset",
                 "layers_done", "tokens_done", "credited", "__weakref__")

    def __init__(self, program: LatentProgram, lane: int, n: int):
        self.program = program
        self.lane = lane
        self.n = n
        L, _, _, H = program.shape
        self.nbytes = L * n * H * program.dtype.itemsize
        self.store = None          # weakref to the adopting store
        self.offset = 0
        self.layers_done = 0
        self.tokens_done = 0       # of layer ``layers_done``
        self.credited = 0          # bytes the ledger already counts
        program.link.captured_bytes += self.nbytes
        program.link.captured_tokens += n

    @property
    def landed(self) -> bool:
        return self.layers_done >= self.program.shape[0]

    @property
    def unread_bytes(self) -> int:
        return self.nbytes - self.credited

    def read(self) -> np.ndarray:
        return self.program.host()[:, self.lane, :self.n]

    def credit(self, nbytes: int, hidden: bool) -> None:
        nbytes = min(nbytes, self.unread_bytes)
        self.credited += nbytes
        link = self.program.link
        if hidden:
            link.landed_hidden_bytes += nbytes
        else:
            link.landed_forced_bytes += nbytes


class PendingLatents:
    """``[L, n, H]`` latents of one sequence, still in the programs that
    made them. Array-like: ``shape``, ``dtype``, ``nbytes``; reading it
    (``np.asarray``, indexing) waits for the copies and gives the same
    bytes the synchronous fetch gave. Several parts in order are a
    chunked prefill's slices."""

    __slots__ = ("parts", "shape", "dtype")

    def __init__(self, parts: List[_Part]):
        self.parts = list(parts)
        L, _, _, H = parts[0].program.shape
        self.shape = (L, sum(p.n for p in parts), H)
        self.dtype = parts[0].program.dtype

    @classmethod
    def joined(cls, chunks) -> "PendingLatents":
        return cls([p for c in chunks for p in c.parts])

    ndim = 3

    @property
    def nbytes(self) -> int:
        return sum(p.nbytes for p in self.parts)

    def __array__(self, dtype=None, copy=None):
        unread = sum(p.unread_bytes for p in self.parts)
        with get_tracer().span("serve.latents.force", bytes=unread,
                               chunks=len(self.parts)) \
                if unread else contextlib.nullcontext():
            views = [p.read() for p in self.parts]
        for p in self.parts:
            p.credit(p.nbytes, hidden=False)
        out = views[0] if len(views) == 1 else \
            np.concatenate(views, axis=1)
        return out.astype(dtype) if dtype is not None and \
            dtype != out.dtype else out

    def __getitem__(self, key):
        return np.asarray(self)[key]


class HostLatentStore:
    """``[L, T, H]`` host latent buffer (layer-major).

    Quacks like the ndarray the restore contract expects: ``.shape`` /
    ``.nbytes`` cover the VALID tokens, and ``np.asarray(store)``
    yields the ``[L, T, H]`` view — so it drops into
    ``engine.restore_kv`` / ``begin_restore`` payload lists unchanged.
    Tokens of pending chunks are valid at once; their bytes are there
    when a reader asks (see the module docstring).
    """

    __slots__ = ("_buf", "_len", "_capacity", "_pending", "__weakref__")

    def __init__(self, first_chunk=None, capacity: Optional[int] = None):
        self._buf: Optional[np.ndarray] = None
        self._len = 0
        #: tokens the owner expects at most; the buffer is sized once
        self._capacity = capacity
        self._pending: deque = deque()      # adopted parts, in order
        if first_chunk is not None:
            self.append(first_chunk)

    @classmethod
    def from_array(cls, arr) -> "HostLatentStore":
        """Rebuild a store around a complete ``[L, T, H]`` latent slab
        (e.g. one that just crossed a wire). Unlike :meth:`append`
        this is not an absorb — no fault site fires — and the slab is
        adopted as the valid span verbatim, preserving dtype."""
        arr = np.ascontiguousarray(arr)
        if arr.ndim != 3:
            raise ValueError(
                f"latent slab must be [L, T, H], got {arr.shape}")
        store = cls()
        if arr.size:
            store._buf = arr
            store._len = arr.shape[1]
        return store

    def append(self, chunk) -> None:
        """Absorb one ``[L, t, H]`` latent chunk (t >= 1): an ndarray is
        copied into place, a :class:`PendingLatents` recorded and landed
        later."""
        from ...resilience.faults import get_injector
        _inj = get_injector()
        if _inj.enabled:
            # before any buffer growth/write: a faulted absorb leaves
            # the store's valid span untouched
            _inj.fire("host.latents", tokens=self._len)
        pending = isinstance(chunk, PendingLatents)
        if not pending:
            chunk = np.asarray(chunk)
        if chunk.ndim != 3:
            raise ValueError(
                f"latent chunk must be [L, t, H], got {chunk.shape}")
        L, t, H = chunk.shape
        if self._buf is None:
            cap = max(t, self._capacity or 16)
            self._buf = np.empty((L, cap, H), chunk.dtype)
        elif (L, H) != (self._buf.shape[0], self._buf.shape[2]):
            raise ValueError(
                f"latent chunk {chunk.shape} does not match store "
                f"layout [L={self._buf.shape[0]}, H={self._buf.shape[2]}]")
        if self._len + t > self._buf.shape[1]:
            cap = self._buf.shape[1]
            while cap < self._len + t:
                cap *= 2
            grown = np.empty((L, cap, H), self._buf.dtype)
            grown[:, :self._len] = self._buf[:, :self._len]
            self._buf = grown
        if pending:
            me = weakref.ref(self)
            for part in chunk.parts:
                part.store, part.offset = me, self._len
                self._pending.append(part)
                self._len += part.n
        else:
            self._buf[:, self._len:self._len + t] = chunk
            self._len += t

    # ------------------------------------------------------------- #
    # landing pending chunks
    # ------------------------------------------------------------- #
    @property
    def pending_bytes(self) -> int:
        return sum(p.unread_bytes for p in self._pending)

    def land(self, part: _Part, hidden: bool) -> int:
        """Copy the next piece of ``part`` into place — whole layers
        while they fit ``LAND_PIECE_BYTES``, else a run of one layer's
        tokens — and return its bytes. A landing that fails truncates
        the store at its last landed token and raises."""
        try:
            src = part.program.host()
        except Exception:
            self._truncate()
            raise
        row = src.shape[3] * src.dtype.itemsize
        layer, at = part.layers_done, part.offset
        if part.n * row <= LAND_PIECE_BYTES:
            upto = min(src.shape[0],
                       layer + LAND_PIECE_BYTES // (part.n * row))
            self._buf[layer:upto, at:at + part.n] = \
                src[layer:upto, part.lane, :part.n]
            part.layers_done = upto
            nbytes = (upto - layer) * part.n * row
        else:
            t0 = part.tokens_done
            t1 = min(part.n, t0 + LAND_PIECE_BYTES // row)
            self._buf[layer, at + t0:at + t1] = \
                src[layer, part.lane, t0:t1]
            part.tokens_done = t1
            if t1 == part.n:
                part.layers_done, part.tokens_done = layer + 1, 0
            nbytes = (t1 - t0) * row
        if part.landed:
            self._pending.remove(part)
        part.credit(nbytes, hidden)
        return nbytes

    def _truncate(self) -> None:
        """Forget the pending chunks and every token from the first of
        them on: what is left is whole, and shorter than its owner's
        count of cached tokens, which every consumer checks."""
        self._len = min([self._len] + [p.offset for p in self._pending])
        for part in self._pending:
            part.store = None
        self._pending.clear()

    def _force(self) -> None:
        """A reader is here: land what is pending, now."""
        if not self._pending:
            return
        with get_tracer().span("serve.latents.force",
                               bytes=self.pending_bytes,
                               chunks=len(self._pending)):
            while self._pending:
                self.land(self._pending[0], hidden=False)

    # ------------------------------------------------------------- #
    # ndarray-compatible surface (the restore payload contract)
    # ------------------------------------------------------------- #
    @property
    def shape(self) -> Tuple[int, int, int]:
        if self._buf is None:
            return (0, 0, 0)
        return (self._buf.shape[0], self._len, self._buf.shape[2])

    @property
    def dtype(self):
        return self._buf.dtype if self._buf is not None else None

    @property
    def nbytes(self) -> int:
        if self._buf is None:
            return 0
        return self._len * self._buf.shape[0] * self._buf.shape[2] * \
            self._buf.dtype.itemsize

    def view(self) -> np.ndarray:
        """Zero-copy ``[L, T, H]`` view of the valid tokens."""
        if self._buf is None:
            raise ValueError("empty HostLatentStore has no view")
        self._force()
        return self._buf[:, :self._len]

    def __array__(self, dtype=None, copy=None):
        v = self.view()
        return v.astype(dtype) if dtype is not None and \
            dtype != v.dtype else v

    def __len__(self) -> int:
        return self._len
