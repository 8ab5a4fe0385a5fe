"""Operations and bytes of a sparse-expert layer's grouped products and
of the paged kernel under a block mask, from shapes alone
(``benchmarks/flops.py``'s conventions: a multiply-add is 2 operations;
bytes are what the algorithm must move through HBM once, inputs read and
outputs written, not what an implementation happens to move). Kept with
the benchmark, beside ``flops.py``, which only a ``benchmark`` PR may
edit.
"""


def expert_ffn_counts(rows, touched, hidden, width, itemsize):
    """The three grouped products of one SwiGLU expert layer: ``rows``
    routed rows (positions times experts a position) by ``W1`` and ``W3``
    (``hidden x width``) and their product by ``W2`` (``width x
    hidden``), over the ``touched`` experts that have a row at all.
    Operations: ``2 * hidden * width`` a row a product. Bytes: each
    touched expert's three matrices read once, and each product's rows
    read and its result written."""
    flops = 3 * 2.0 * rows * hidden * width
    bytes_ = (touched * 3.0 * hidden * width
              + 3.0 * rows * (hidden + width)) * itemsize
    return {"flops": flops, "bytes": bytes_}


def touched_experts(rows, n_experts):
    """Experts that get a row when ``rows`` picks fall evenly at random
    on ``n_experts``: what a call is counted with where the program
    counted none (a prompt slice)."""
    return n_experts * (1.0 - (1.0 - 1.0 / n_experts) ** rows)


def paged_block_counts(context_lens, q_lens, n_head, n_kv_head, head_dim,
                       itemsize, block):
    """Paged attention of one dispatch under the block mask: lane i
    attends ``q_lens[i]`` new rows (whole blocks of ``block``) over a
    context of ``context_lens[i]`` cached tokens, the new ones included;
    a row sees every token before the end of its own block. Operations:
    QK^T and PV, ``4 * d`` a pair a head; a lane of one block pairs every
    row with the whole context, a longer one loses the triangle of whole
    blocks above its rows. Bytes: each lane's K and V read once from the
    pool, Q read, O written."""
    flops = bytes_ = 0.0
    for ctx, q in zip(context_lens, q_lens):
        blocks = q // block
        pairs = q * ctx - block * block * blocks * (blocks - 1) / 2.0
        flops += 4.0 * n_head * head_dim * pairs
        bytes_ += 2.0 * ctx * n_kv_head * head_dim * itemsize \
            + 2.0 * q * n_head * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}
