"""Per-sequence host-side state.

Reference analog: ``deepspeed/inference/v2/ragged/sequence_descriptor.py``
``DSSequenceDescriptor`` — tracks seen tokens, in-flight tokens and the KV
block ids of one sequence (there mirrored into device tensors; on TPU only
the block table is shipped, as gather indices at batch build time).
"""

from typing import List


class SequenceDescriptor:

    def __init__(self, uid: int):
        self.uid = uid
        self.seen_tokens = 0            # tokens whose KV is materialized
        self.in_flight_tokens = 0       # tokens in the current forward
        self.blocks: List[int] = []     # KV pool block ids, in order
        #: a trunk with window layers keeps their K and V in a pool of
        #: its own (``StateManager``): the blocks of that pool this
        #: sequence holds, for its logical blocks ``window_first``
        #: onwards; those before went back to the allocator when they
        #: fell wholly behind the window
        self.window_blocks: List[int] = []
        self.window_first = 0
        #: slot of the recurrent-state pools this sequence holds from its
        #: first scheduling to its flush (-1: the model has no recurrent
        #: layer, see ``StateManager``)
        self.state_slot = -1
        #: host copy of the KV while suspended (engine.suspend_sequence;
        #: reference: BlockedKVCache's host-offloaded blocks)
        self.host_kv = None
        #: token ids whose KV this sequence holds — maintained only when
        #: prefix caching is on (feeds the chained block index; a
        #: restore_kv-built sequence leaves it short of seen_tokens,
        #: which excludes it from registration)
        self.history: List[int] = []
        #: full blocks counted at the last prefix-index walk (skip
        #: rewalking on every decode token), plus the chain position the
        #: walk ended at — valid only while the engine's index epoch
        #: matches (purges invalidate cached chain tips)
        self.registered_full = 0
        self.chain_parent = -1
        self.chain_epoch = 0

    @property
    def cur_allocated_blocks(self) -> int:
        return len(self.blocks)

    def extend_blocks(self, new_blocks: List[int]) -> None:
        self.blocks.extend(new_blocks)

    def pre_forward(self, num_tokens: int) -> None:
        self.in_flight_tokens = num_tokens

    def post_forward(self) -> None:
        self.seen_tokens += self.in_flight_tokens
        self.in_flight_tokens = 0

    def rollback(self, num_tokens: int) -> None:
        """Un-count the last ``num_tokens`` cached tokens (speculative
        decoding: rejected draft KV). The physical slots keep their
        stale values but sit past ``seen_tokens`` so no attention reads
        them, and the next dispatch overwrites the same positions;
        blocks stay allocated (they are about to be refilled)."""
        if self.in_flight_tokens:
            raise RuntimeError("rollback during an in-flight forward")
        if not 0 <= num_tokens <= self.seen_tokens:
            raise ValueError(
                f"rollback({num_tokens}) with seen={self.seen_tokens}")
        self.seen_tokens -= num_tokens

    def __repr__(self):
        return (f"SequenceDescriptor(uid={self.uid}, "
                f"seen={self.seen_tokens}, blocks={len(self.blocks)})")
