"""Operations and bytes of the gated-delta-rule kernels, from shapes
alone (``benchmarks/flops.py``'s conventions: a multiply-add is 2
operations; bytes are what the algorithm must move through HBM once,
inputs read and outputs written, not what an implementation happens to
move). Kept with the benchmark, beside ``flops.py``, which only a
``benchmark`` PR may edit.

The rule, per head, with a ``[d_k, d_v]`` state ``S``: ``S <- e^g S``;
``u = beta (v - S^T k)``; ``S <- S + k u^T``; ``o = S^T q``.
"""


def gated_delta_step_counts(lanes, n_head, d_k, d_v, itemsize,
                            state_itemsize=4):
    """The rule at one token a lane (a decode dispatch). Operations per
    lane and head: the decay (``d_k d_v``), ``S^T k``, ``k u^T`` and
    ``S^T q`` (``2 d_k d_v`` each). Bytes: each lane's state read and
    written once, q, k, v read, o written."""
    flops = lanes * n_head * 7.0 * d_k * d_v
    bytes_ = lanes * n_head * (
        2.0 * d_k * d_v * state_itemsize
        + (2.0 * d_k + 2.0 * d_v) * itemsize)
    return {"flops": flops, "bytes": bytes_}


def gated_delta_chunk_counts(t_lens, n_head, d_k, d_v, chunk, itemsize,
                             state_itemsize=4):
    """The chunked rule over lanes of ``t_lens`` real tokens (pads do no
    work the algorithm needs). A chunk of ``c`` tokens, per head:
    ``K K^T`` below the diagonal and ``Q K^T`` on and below it
    (``2 d_k`` a pair), ``K S_0`` and ``Q S_0`` (``2 c d_k d_v`` each),
    the forward substitution on the ``[c, d_v]`` right-hand side and the
    product of the masked ``Q K^T`` with its result (``2 d_v`` a pair),
    ``K^T U`` into the state (``2 c d_k d_v``) and the state's decay
    (``d_k d_v``). Bytes: q, k, v read and o written for every real
    token, each lane's state read and written once a call."""
    flops = bytes_ = 0.0
    for t in t_lens:
        left = int(t)
        while left > 0:
            c = min(chunk, left)
            below, upto = c * (c - 1) / 2.0, c * (c + 1) / 2.0
            flops += n_head * (
                2.0 * d_k * (below + upto) + 2.0 * d_v * (below + upto)
                + 6.0 * c * d_k * d_v + d_k * d_v)
            left -= c
        bytes_ += n_head * (
            t * (2.0 * d_k + 2.0 * d_v) * itemsize
            + 2.0 * d_k * d_v * state_itemsize)
    return {"flops": flops, "bytes": bytes_}
