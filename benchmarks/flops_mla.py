"""Operations and bytes of latent attention over a pool of compressed KV
rows, from shapes alone (``benchmarks/flops.py``'s conventions: a
multiply-add is 2 operations; bytes are what the algorithm must move
through HBM once, inputs read and outputs written, not what an
implementation happens to move). Kept with the benchmark, beside
``flops.py``, which only a ``benchmark`` PR may edit.

The form counted is the one the program keeps, the **absorbed** one
(``ops/latent_attention.py``): a head's query is ``[q~ | q_rope]``, ``C +
R`` wide, against the cached row ``[c | r]``, and its result ``sum_s p_s
c_s``, ``C`` wide: ``2 * (C + R) + 2 * C`` operations a (row, visible
position) pair a head, 2,176 at the published 512 + 64. The absorb and
un-absorb products around the kernel (``W_uk``, ``W_uv``) are not the
kernel's and are not counted here.
"""


def latent_attention_counts(context_lens, q_lens, n_head, c_width, r_width,
                            itemsize):
    """Latent attention of one dispatch: lane i attends ``q_lens[i]`` new
    rows over a context of ``context_lens[i]`` cached positions, the new
    ones included, causally: a lane's pairs are ``q * ctx - q * (q - 1) /
    2``. Bytes: each lane's cached rows ``[c | r]`` read once from the
    pool (one row serves every head, as key and as value; ``r_width`` is
    the rotary key's own width, not its pool's padding), the absorbed
    queries read and the results written."""
    flops = bytes_ = 0.0
    for ctx, q in zip(context_lens, q_lens):
        pairs = q * ctx - q * (q - 1) / 2.0
        flops += n_head * pairs * 2.0 * ((c_width + r_width) + c_width)
        bytes_ += (ctx * (c_width + r_width)
                   + q * n_head * ((c_width + r_width) + c_width)) * itemsize
    return {"flops": flops, "bytes": bytes_}
