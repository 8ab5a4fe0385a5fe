"""Operations and bytes of paged attention under a sliding window, and
of an expert layer's grouped products over the experts a chip holds,
from shapes alone (``benchmarks/flops.py``'s conventions: a multiply-add
is 2 operations; bytes are what the algorithm must move through HBM
once, inputs read and outputs written, not what an implementation
happens to move). Kept with the benchmark, beside ``flops.py``, which
only a ``benchmark`` PR may edit.
"""

from .flops_moe import expert_ffn_counts


def window_pairs(ctx, q, window):
    """The (query row, visible key) pairs of a lane that attends ``q``
    new rows ending a context of ``ctx`` tokens (the new ones included)
    under ``window``: the row at position ``p`` sees ``min(p + 1,
    window)`` keys, exactly."""
    first = ctx - q                     # position of the first new row
    # rows whose whole window lies inside the context see ``window``
    full = max(0, min(q, ctx - max(first, window - 1)))
    ramp = q - full                     # rows at positions < window - 1
    # those see position + 1 keys: first + 1 ... first + ramp
    return full * window + ramp * (first + 1) + ramp * (ramp - 1) / 2.0


def window_attention_counts(context_lens, q_lens, n_head, n_kv_head,
                            head_dim, itemsize, window):
    """Paged attention of one dispatch under a sliding window: lane i
    attends ``q_lens[i]`` new rows over a context of ``context_lens[i]``
    cached tokens, each row seeing the ``window`` positions up to its
    own. Operations: QK^T and PV, ``4 * d`` a pair a head
    (:func:`window_pairs`). Bytes: the K and V rows any of the lane's
    rows sees, read once from the pool (``min(ctx, window + q - 1)``
    positions), Q read, O written."""
    flops = bytes_ = 0.0
    for ctx, q in zip(context_lens, q_lens):
        flops += 4.0 * n_head * head_dim * window_pairs(ctx, q, window)
        seen = min(ctx, window + q - 1)
        bytes_ += 2.0 * seen * n_kv_head * head_dim * itemsize \
            + 2.0 * q * n_head * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}


def held_expert_counts(rows, touched, hidden, width, itemsize):
    """The three grouped products of an expert layer that holds a share
    of its experts: ``rows`` the routed rows that fell on held experts,
    ``touched`` the held experts with a row at all
    (``flops_moe.expert_ffn_counts`` over those alone: a row routed to
    an expert on another chip costs this chip no product and no
    weight)."""
    return expert_ffn_counts(rows, touched, hidden, width, itemsize)
