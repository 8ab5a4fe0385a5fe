"""``packed_stream``: a pretraining job's input.

Documents of heavy-tailed length (log-normal), each ended by an
end-of-document id, are concatenated and cut into full sequences, as a
pretraining loader packs them: no padding, document boundaries inside a
sequence. Token ids are seeded. ``batches`` is an endless iterator of
``{"input_ids": int32[global_batch, seq_len]}``; the runner keeps it
running in a thread beside the steps, as a job's input pipeline does.
"""

import numpy as np


def batches(traffic, seed, vocab_size):
    seq_len = int(traffic["seq_len"])
    global_batch = int(traffic["global_batch"])
    doc = traffic["document_tokens"]
    eod = int(traffic.get("eod_id", 2))
    rng = np.random.default_rng([int(seed), 11])
    need = seq_len * global_batch
    buf = np.empty(0, np.int32)
    while True:
        while buf.size < need:
            n = int(np.clip(rng.lognormal(np.log(doc["median"]),
                                          doc["sigma"]),
                            doc["min"], doc["max"]))
            ids = rng.integers(0, vocab_size, n, dtype=np.int32)
            ids[-1] = eod
            buf = np.concatenate([buf, ids])
        out, buf = buf[:need], buf[need:]
        yield {"input_ids": out.reshape(global_batch, seq_len).copy()}
