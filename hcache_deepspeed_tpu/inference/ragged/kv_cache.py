"""Blocked (paged) KV cache + host-side state manager.

Reference analogs:
* ``deepspeed/inference/v2/ragged/kv_cache.py:40 BlockedKVCache`` — the
  device block pool,
* ``deepspeed/inference/v2/ragged/ragged_manager.py:19 DSStateManager`` —
  uid → sequence tracking plus allocator wiring.

TPU-native layout: one pool per k/v of shape ``[L, KV, P, D]`` with
``P = num_blocks * block_size`` token slots, kept as jnp arrays that flow
*functionally* through the jitted forward. They are donated, ride whole in
the carry of the layer loop, are written in place (``ops/kv_write.py``:
a decode lane's one row by a scatter of ``[D]`` rows at ``[layer, head,
slot]``, a prompt slice's rows a block run at a time by a kernel that
takes the pool as an aliased operand) and read by the paged kernel at a
layer index (``inference/model.py _trunk``), so the compiled program
holds one buffer a pool and updates it in place in HBM: no layer is ever
sliced out of it (``tests/unit/inference/test_kv_pool_in_place.py`` reads
that off the compiled program). Head-major (KV before P) so the paged-attention
kernel's per-(head, block) DMA tile is ``[block_size, D]`` — a legal Mosaic
tile whose last two dims match the array's minor dims; token-major would
force an un-tileable ``[BS, 1, D]`` block. Block granularity exists in
the host-side allocator, in the paged kernel's index map and in the write
of a lane that carries more than one position, which is whole blocks but
for its two ends.
"""

import re
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blocked_allocator import BlockedAllocator
from .sequence import SequenceDescriptor


#: an instruction with an array result: (name, result dimensions, opcode)
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", re.M)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", re.M)


def _pool_sized(hlo_text: str, pool_shape, ops) -> List[str]:
    """The instructions of ``ops`` whose result has as many elements as
    the ``[L, KV, P, D]`` pool or one layer of it."""
    extents = {int(np.prod(pool_shape)), int(np.prod(pool_shape[1:]))}
    return [f"{op} {name} [{dims}]"
            for name, dims, op in _HLO_INSTRUCTION.findall(hlo_text)
            if op in ops
            and int(np.prod([int(d) for d in dims.split(",")])) in extents]


def pool_sized_copies(hlo_text: str, pool_shape) -> List[str]:
    """The ``copy``, ``dynamic-slice`` and ``dynamic-update-slice``
    instructions of an optimised program (fused ones too) whose result
    has as many elements as the ``[L, KV, P, D]`` pool or one layer of
    it, whatever dimensions spell them. A forward that holds the pool in
    place has none; each one found moves a layer's cache, or all of it,
    through HBM once per execution."""
    return _pool_sized(hlo_text, pool_shape,
                       ("copy", "dynamic-slice", "dynamic-update-slice"))


def pool_scatters(hlo_text: str, pool_shape) -> List[str]:
    """The ``scatter`` instructions of an optimised program (fused ones
    too) that write into the ``[L, KV, P, D]`` pool, found like
    :func:`pool_sized_copies` by their result's extent. That is the row
    write (``ops/kv_write.py write_rows``): one ``[D]`` row an update,
    right for a decode program's one row a lane; a program whose lanes
    carry more positions writes by block runs and has none."""
    return _pool_sized(hlo_text, pool_shape, ("scatter",))


def stacked_layer_copies(hlo_text: str, stacked_shapes) -> List[str]:
    """The ``copy``, ``fusion`` and ``dynamic-slice`` instructions of an
    optimised program that run as operations of their own (outside every
    fused computation: what a device trace names) and whose result is
    one layer ``[1, ...]`` of a stacked parameter leaf of
    ``stacked_shapes`` (``[L, ...]`` each): a slice of the leaf into a
    second buffer, or a copy of that buffer into another layout. A layer
    scan whose matmuls read their layer of the leaf inside their own
    fusion has none; each one found is one more pass over that layer's
    weights in every execution."""
    layers = {(1,) + tuple(shape[1:]) for shape in stacked_shapes}
    heads = list(_HLO_COMPUTATION.finditer(hlo_text))
    found = []
    for head, following in zip(heads, heads[1:] + [None]):
        if head.group(1).startswith("fused_computation"):
            continue
        body = hlo_text[head.end():following.start() if following else None]
        found += [f"{op} {name} [{dims}]"
                  for name, dims, op in _HLO_INSTRUCTION.findall(body)
                  if op in ("copy", "fusion", "dynamic-slice")
                  and tuple(int(d) for d in dims.split(",")) in layers]
    return found


class BlockedKVCache:
    """Device block pool for all layers of one model."""

    def __init__(self, n_layers: int, num_blocks: int, block_size: int,
                 n_kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
                 sharding=None, v_head_dim: Optional[int] = None):
        """``v_head_dim``: the width of ``v``'s rows where it is not
        ``k``'s (a latent-attention trunk: ``k`` holds the compressed KV
        rows and ``v`` the narrower rotary keys, ``model_latent.py``)."""
        self.n_layers = n_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.v_head_dim = head_dim if v_head_dim is None else v_head_dim
        self.dtype = dtype
        shape = (n_layers, n_kv_heads, num_blocks * block_size)
        k = jnp.zeros(shape + (head_dim,), dtype)
        v = jnp.zeros(shape + (self.v_head_dim,), dtype)
        if sharding is not None:
            k = jax.device_put(k, sharding)
            v = jax.device_put(v, sharding)
        self.k = k
        self.v = v

    @staticmethod
    def token_bytes(n_layers: int, n_kv_heads: int, head_dim: int,
                    dtype, v_head_dim: Optional[int] = None) -> int:
        """KV bytes per cached token (k + v across all layers)."""
        v_head_dim = head_dim if v_head_dim is None else v_head_dim
        return (n_layers * n_kv_heads * (head_dim + v_head_dim) *
                jnp.dtype(dtype).itemsize)

    @property
    def per_token_bytes(self) -> int:
        return self.token_bytes(self.n_layers, self.n_kv_heads,
                                self.head_dim, self.dtype, self.v_head_dim)

    def replace(self, k, v):
        self.k, self.v = k, v


class HybridCache(BlockedKVCache):
    """The block pool of a hybrid trunk's full-attention layers, and
    beside it the slot pools of its recurrent layers: ``state``
    ``[L_lin, slots + 1, H, d_k, d_v]`` float32 and ``conv``
    ``[L_lin, slots + 1, (K - 1) * C]`` (the convolution's last ``K - 1``
    inputs, flat: a minor dimension of three rows makes the TPU compiler
    lay the pool out afresh at every program's entry and exit).
    A sequence holds one slot of both, every recurrent layer's row of
    it, from admission to flush; slot ``slots`` is spare: blank lanes of
    a dispatch bucket read and write it. All four arrays are donated to
    every forward and replaced by its results."""

    def __init__(self, n_full_layers: int, num_blocks: int,
                 block_size: int, n_kv_heads: int, head_dim: int, *,
                 n_linear_layers: int, state_slots: int, n_heads: int,
                 key_dim: int, value_dim: int, conv_taps: int,
                 conv_channels: int, dtype=jnp.bfloat16):
        super().__init__(n_full_layers, num_blocks, block_size,
                         n_kv_heads, head_dim, dtype=dtype)
        self.state_slots = state_slots
        self.state = jnp.zeros((n_linear_layers, state_slots + 1, n_heads,
                                key_dim, value_dim), jnp.float32)
        self.conv = jnp.zeros((n_linear_layers, state_slots + 1,
                               (conv_taps - 1) * conv_channels), dtype)

    @property
    def slot_bytes(self) -> int:
        """Bytes one sequence's slot holds, all recurrent layers."""
        return (self.state.nbytes + self.conv.nbytes) // \
            (self.state_slots + 1)

    def replace_state(self, state, conv):
        self.state, self.conv = state, conv


class WindowedKVCache(BlockedKVCache):
    """Two block pools for a trunk whose layers are of two kinds: the
    global (full-attention) layers' K and V in ``k``/``v`` ``[L_global,
    KV, P_g, D]`` and the window layers' in ``wk``/``wv`` ``[L_window,
    KV, P_w, D]``, each with its own allocator and block lifetime
    (``StateManager``): a window layer's blocks go back while the
    sequence lives, so ``P_w`` is sized by the window and not by the
    context. All four arrays are donated to every forward and replaced
    by its results."""

    def __init__(self, n_global_layers: int, num_blocks: int,
                 n_window_layers: int, num_window_blocks: int,
                 block_size: int, n_kv_heads: int, head_dim: int,
                 dtype=jnp.bfloat16):
        super().__init__(n_global_layers, num_blocks, block_size,
                         n_kv_heads, head_dim, dtype=dtype)
        self.n_window_layers = n_window_layers
        self.num_window_blocks = num_window_blocks
        shape = (n_window_layers, n_kv_heads,
                 num_window_blocks * block_size, head_dim)
        self.wk = jnp.zeros(shape, dtype)
        self.wv = jnp.zeros(shape, dtype)

    def replace_window(self, wk, wv):
        self.wk, self.wv = wk, wv


def window_of(model_config) -> int:
    """The sliding window of a trunk that has window
    (``sliding_attention``) layers beside global ones and so keeps two
    block pools, read off its shape; 0 for any other."""
    types = getattr(model_config, "layer_types", None) or ()
    window = getattr(model_config, "sliding_window", None)
    return int(window) if window and "sliding_attention" in types else 0


class StateManager:
    """uid → SequenceDescriptor tracking + block budget arithmetic, and
    for a trunk with recurrent layers the slots of its state pools
    (``state_slots`` of them; 0: no such layer, nothing is kept).

    ``window_blocks`` > 0: the trunk's window layers keep their K and V
    in a second pool of that many blocks (``WindowedKVCache``), with its
    own allocator and a block lifetime of its own: after a step, a
    sequence's window blocks that lie wholly behind ``seen_tokens -
    window`` return to the allocator (:meth:`release_behind_window`),
    while its global blocks stay until the flush. Every budget question
    (:meth:`has_room`) is asked of both pools."""

    def __init__(self, max_tracked_sequences: int, num_blocks: int,
                 block_size: int, max_seq_len: int, state_slots: int = 0,
                 window_blocks: int = 0, window: int = 0):
        self.max_tracked_sequences = max_tracked_sequences
        self.block_size = block_size
        self.max_seq_len = max_seq_len
        self.allocator = BlockedAllocator(num_blocks)
        self._seqs: Dict[int, SequenceDescriptor] = {}
        self.state_slots = state_slots
        self._free_slots: List[int] = list(range(state_slots))
        self.window = window
        self.window_allocator = BlockedAllocator(window_blocks) \
            if window_blocks else None
        #: window blocks given back while their sequence lived
        self.window_blocks_released = 0

    @property
    def free_state_slots(self) -> int:
        return len(self._free_slots)

    @property
    def state_slots_in_use(self) -> int:
        return self.state_slots - len(self._free_slots)

    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def get_sequence(self, uid: int) -> Optional[SequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid: int) -> SequenceDescriptor:
        seq = self._seqs.get(uid)
        if seq is None:
            if len(self._seqs) >= self.max_tracked_sequences:
                raise RuntimeError(
                    f"sequence limit {self.max_tracked_sequences} reached")
            if self.state_slots and not self._free_slots:
                raise RuntimeError(
                    f"no free recurrent-state slot of {self.state_slots}")
            seq = SequenceDescriptor(uid)
            if self.state_slots:
                seq.state_slot = self._free_slots.pop()
            self._seqs[uid] = seq
        return seq

    def blocks_needed(self, seq: Optional[SequenceDescriptor],
                      new_tokens: int) -> int:
        seen = seq.seen_tokens if seq else 0
        have = seq.cur_allocated_blocks if seq else 0
        total = seen + new_tokens
        need = -(-total // self.block_size)  # ceil
        return max(need - have, 0)

    # ---- the window layers' pool ---------------------------------- #
    @property
    def free_window_blocks(self) -> int:
        return self.window_allocator.free_blocks \
            if self.window_allocator else 0

    def _first_live(self, seen: int) -> int:
        """The first logical block a query at ``seen`` or later can
        see under the window."""
        return max(seen - self.window, 0) // self.block_size

    def window_blocks_needed(self, seq: Optional[SequenceDescriptor],
                             new_tokens: int, behind: bool = True) -> int:
        """Blocks of the window pool a forward of ``new_tokens`` more
        positions takes: the logical blocks up to its end that the
        sequence does not hold, at most ``ceil((window + new_tokens) /
        block_size) + 1`` once the blocks behind the window have gone
        back. ``behind`` false: the forward writes only the rows still
        inside the window at its end (a restore), and a sequence that
        holds nothing starts at the first block those lie in."""
        if self.window_allocator is None:
            return 0
        seen = seq.seen_tokens if seq else 0
        have = len(seq.window_blocks) if seq else 0
        need = -(-(seen + new_tokens) // self.block_size)
        return max(need - self._window_first(seq, new_tokens, behind)
                   - have, 0)

    def _window_first(self, seq, new_tokens: int, behind: bool) -> int:
        """The logical block ``seq``'s window table starts at for a
        forward of ``new_tokens``: where it stands, or (``behind``
        false, nothing held yet) the first block a query past the
        forward's end still sees."""
        first = seq.window_first if seq else 0
        if behind or (seq and seq.window_blocks):
            return first
        seen = seq.seen_tokens if seq else 0
        return max(first, self._first_live(seen + new_tokens))

    def has_room(self, asks, behind: bool = True,
                 slice_tokens: int = 0) -> bool:
        """Whether both pools hold the blocks that ``asks``, pairs of
        (sequence or ``None``, new tokens), need together.
        ``slice_tokens`` > 0: a forward holds at most that many tokens
        of a sequence (chunked prefill) and the blocks behind the window
        go back between forwards, so the window pool is asked for a
        slice."""
        asks = list(asks)
        if sum(self.blocks_needed(seq, n) for seq, n in asks) > \
                self.free_blocks:
            return False
        if self.window_allocator is None:
            return True

        def tokens(n):
            return min(n, slice_tokens) if slice_tokens and behind else n
        return sum(self.window_blocks_needed(seq, tokens(n), behind)
                   for seq, n in asks) <= self.free_window_blocks

    def release_behind_window(self, seq: SequenceDescriptor) -> int:
        """Give back ``seq``'s window blocks that lie wholly behind
        ``seen_tokens - window``: no later query reaches them, and the
        kernel's walk starts past their table entries. Returns how
        many."""
        if self.window_allocator is None:
            return 0
        gone = min(self._first_live(seq.seen_tokens) - seq.window_first,
                   len(seq.window_blocks))
        if gone <= 0:
            return 0
        self.window_allocator.free(seq.window_blocks[:gone])
        del seq.window_blocks[:gone]
        seq.window_first += gone
        self.window_blocks_released += gone
        return gone

    def pool_stats(self) -> Dict[str, Dict[str, int]]:
        """Blocks, blocks in use and the most in use of each pool, and
        the window blocks given back behind windows."""
        def of(alloc):
            return {"blocks": alloc.num_blocks,
                    "in_use": alloc.num_blocks - alloc.free_blocks,
                    "peak_in_use": alloc.peak_in_use}
        out = {"global": of(self.allocator)}
        if self.window_allocator is not None:
            out["window"] = dict(of(self.window_allocator),
                                 released=self.window_blocks_released)
        return out

    def maybe_allocate_kv(self, seq: SequenceDescriptor,
                          new_tokens: int, behind: bool = True) -> None:
        need = self.blocks_needed(seq, new_tokens)
        if need:
            seq.extend_blocks(self.allocator.allocate(need))
        need = self.window_blocks_needed(seq, new_tokens, behind)
        if need:
            seq.window_first = self._window_first(seq, new_tokens, behind)
            seq.window_blocks.extend(self.window_allocator.allocate(need))

    def flush_sequence(self, uid: int) -> None:
        seq = self._seqs.pop(uid, None)
        if seq is None:
            return
        if seq.blocks:
            self.allocator.free(seq.blocks)
        if seq.window_blocks:
            self.window_allocator.free(seq.window_blocks)
            seq.window_blocks = []
        if seq.state_slot >= 0:
            self._free_slots.append(seq.state_slot)
            seq.state_slot = -1

    def block_table(self, seq: SequenceDescriptor,
                    max_blocks: int) -> np.ndarray:
        """Padded int32 block table; unused entries point at block 0 but are
        never read/written thanks to length masks. With a window pool
        the window table follows the global one, ``max_blocks`` entries
        each by logical block: the entries behind the window, whose
        blocks have gone back, are never read either."""
        table = np.zeros((max_blocks * (1 + bool(self.window_allocator)),),
                         np.int32)
        n = min(len(seq.blocks), max_blocks)
        table[:n] = seq.blocks[:n]
        if self.window_allocator is not None:
            at = max_blocks + seq.window_first
            held = seq.window_blocks[:2 * max_blocks - at]
            table[at:at + len(held)] = held
        return table
