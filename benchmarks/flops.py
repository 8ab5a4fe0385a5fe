"""Operations and bytes of the kernels and of a training step, from
shapes alone. Kept with the benchmark: a roofline share or an MFU is
only as honest as these counts, so no PR that claims a gain may touch
them.

Conventions: a multiply-add is 2 operations; bytes are what the
algorithm must move through HBM once (inputs read, outputs written), not
what an implementation happens to move.
"""


def train_flops_per_token(arch, seq_len):
    """Forward + backward matrix operations per token of a llama trunk,
    no recomputation counted, embedding lookup not counted (it is a
    gather). Forward is 2 operations per weight per token plus causal
    attention's 2 * 2 * hidden * (seq_len / 2) per layer (QK^T and PV
    over the half of the square that causality keeps); backward is twice
    the forward."""
    h = arch["hidden_size"]
    ffn = arch["intermediate_size"]
    head = h // arch["num_attention_heads"]
    kv = arch["num_key_value_heads"] * head
    per_layer_weights = h * h + 2 * h * kv + h * h + 3 * h * ffn
    weights = arch["num_hidden_layers"] * per_layer_weights \
        + h * arch["vocab_size"]                      # the head
    attn = arch["num_hidden_layers"] * 2 * 2 * h * (seq_len / 2.0)
    return 3.0 * (2.0 * weights + attn)


def flash_attention_counts(batch, q_len, kv_len, n_head, n_kv_head,
                           head_dim, itemsize, causal=True,
                           backward=False):
    """Flash attention over ``[batch, q_len, n_head, head_dim]`` against
    ``kv_len`` keys. Forward: QK^T and PV, 4 * q * kv * d per head,
    halved under a causal mask over a square. Backward recomputes QK^T
    and forms dV, dP, dQ, dK: five matrix products against the forward's
    two. Bytes forward: Q, K, V read, O written; backward: Q, K, V, O,
    dO read, dQ, dK, dV written."""
    share = 0.5 if causal and q_len == kv_len else 1.0
    mm = 2.0 * batch * n_head * q_len * kv_len * head_dim * share
    q_bytes = batch * q_len * n_head * head_dim * itemsize
    kv_bytes = batch * kv_len * n_kv_head * head_dim * itemsize
    if backward:
        return {"flops": 5.0 * mm, "bytes": 4.0 * q_bytes + 4.0 * kv_bytes}
    return {"flops": 2.0 * mm, "bytes": 2.0 * q_bytes + 2.0 * kv_bytes}


def paged_attention_counts(context_lens, q_lens, n_head, n_kv_head,
                           head_dim, itemsize):
    """Paged attention of one dispatch: lane i attends ``q_lens[i]`` new
    query rows over a context of ``context_lens[i]`` cached tokens (the
    new ones included). Operations: QK^T and PV, 4 * q * ctx * d per
    head, with the causal triangle among the new rows taken off. Bytes:
    each lane's K and V read once from the pool, Q read, O written."""
    flops = bytes_ = 0.0
    for ctx, q in zip(context_lens, q_lens):
        pairs = q * ctx - q * (q - 1) / 2.0
        flops += 4.0 * n_head * head_dim * pairs
        bytes_ += 2.0 * ctx * n_kv_head * head_dim * itemsize \
            + 2.0 * q * n_head * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(counts, peaks):
    """The least time the chip could take, and which limit binds."""
    t_flops = counts["flops"] / (peaks["bf16_tflops"] * 1e12)
    t_bytes = counts["bytes"] / (peaks["hbm_gbps"] * 1e9)
    return (max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "memory")
