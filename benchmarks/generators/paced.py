"""``paced``: an open loop whose work does not depend on the seed.

Two things make a serving run repeat, and both are here and not in the
system under test.

* Arrivals are paced, not drawn: the i-th request of the window is due
  at ``(i + 0.5) / rate`` after the window opens, plus a seeded jitter
  uniform in ``+-jitter / rate`` (``jitter`` under 0.5, so a request
  never leaves its slot). The number of requests due inside a window of
  ``seconds`` is ``floor(rate * seconds)`` for every seed.
* Lengths are stratified, not sampled: within every block of ``block``
  consecutive requests the k-th smallest prompt (and, independently,
  output) length is the ``(k + 0.5) / block`` quantile of the stated
  distribution. Every block brings the same prompt tokens and the same
  output tokens in every run, and the heavy tail is kept.
* The seed changes as little of the work as it can. The order of a
  block's prompt lengths is fixed by the block's number alone, because
  which long prompts meet in one step sets the tail of the time to
  first token: with the order drawn from the seed, runs of different
  seeds differed by 15% in ``ttft_p90_s`` and by 3% in tokens/s where
  two runs of one seed differed by 2% and 0.3% (PR 23, chip). The seed
  permutes the output lengths within groups of ``shuffle`` consecutive
  requests of a fixed order, draws the jitter and chooses the token
  ids.

Blocks are laid from the opening of the window, forwards and backwards,
so a window of a whole number of blocks holds whole blocks. The same
schedule runs for ``ramp_s`` seconds before the window opens (requests
with a negative index), so that the window opens on a system at its
standing load; nothing of the ramp is counted.
"""

import math
import statistics
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Arrival:
    index: int            # 0 is the first request due in the window
    due_s: float          # seconds after the start of the ramp
    prompt: tuple         # token ids
    max_new_tokens: int
    priority: int = 0

    @property
    def in_window(self):
        return self.index >= 0


def quantile_lengths(spec, n):
    """The ``(k + 0.5) / n`` quantiles, k = 0..n-1, of a length
    distribution, as whole numbers inside ``[min, max]``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    ps = [(k + 0.5) / n for k in range(n)]
    if spec["dist"] == "lognormal":
        normal = statistics.NormalDist()
        raw = [spec["median"] * math.exp(spec["sigma"] * normal.inv_cdf(p))
               for p in ps]
    elif spec["dist"] == "uniform":
        raw = [lo + (hi - lo) * p for p in ps]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [min(max(int(round(x)), lo), hi) for x in raw]


#: the seed of the orders that every run shares
_ORDER_SEED = 0xB10C


def _block_rng(seed, block_id, stream):
    return np.random.default_rng([int(seed), int(block_id) & 0xFFFFFFFF,
                                  stream])


def schedule(traffic, seed, seconds, vocab_size, max_context):
    """Every arrival of one run, ramp first, in due order."""
    rate = float(traffic["rate"])
    ramp_s = float(traffic["ramp_s"])
    block = int(traffic["block"])
    jitter = float(traffic.get("jitter", 0.25))
    if not 0 <= jitter < 0.5:
        raise ValueError("jitter must be in [0, 0.5): a request stays in "
                         "its own slot of the schedule")
    prompts = quantile_lengths(traffic["prompt_tokens"], block)
    outputs = quantile_lengths(traffic["output_tokens"], block)
    n_window = int(math.floor(rate * seconds + 1e-9))
    n_ramp = int(math.floor(rate * ramp_s + 1e-9))

    shuffle = max(1, int(traffic.get("shuffle", 5)))
    perms = {}

    def block_perm(block_id):
        if block_id not in perms:
            # the orders every seed shares
            p_perm = _block_rng(_ORDER_SEED, block_id, 1).permutation(block)
            o_perm = _block_rng(_ORDER_SEED, block_id, 2).permutation(block)
            # the seed's own: outputs move within groups of ``shuffle``
            rng = _block_rng(seed, block_id, 2)
            for lo in range(0, block, shuffle):
                hi = min(lo + shuffle, block)
                o_perm[lo:hi] = o_perm[lo:hi][rng.permutation(hi - lo)]
            perms[block_id] = (
                p_perm, o_perm,
                _block_rng(seed, block_id, 3).uniform(-jitter, jitter,
                                                      block))
        return perms[block_id]

    out = []
    for i in range(-n_ramp, n_window):
        block_id, k = divmod(i, block)          # floors for negative i
        p_perm, o_perm, jit = block_perm(block_id)
        n_prompt = prompts[p_perm[k]]
        n_out = min(outputs[o_perm[k]], max_context - n_prompt)
        ids = np.random.default_rng(
            [int(seed), i & 0xFFFFFFFF, 4]).integers(0, vocab_size, n_prompt)
        out.append(Arrival(
            index=i, due_s=ramp_s + (i + 0.5 + float(jit[k])) / rate,
            prompt=tuple(int(t) for t in ids), max_new_tokens=int(n_out),
            priority=int(traffic.get("priority", 0))))
    return out


def prefill_slices(traffic, chunk):
    """The prompt-slice lengths this mix can put into one dispatch: whole
    chunks and each prompt's tail. Set-up warms the shapes they reach."""
    lengths = set()
    for n in quantile_lengths(traffic["prompt_tokens"], int(traffic["block"])):
        if chunk and n > chunk:
            lengths.add(chunk)
            if n % chunk:
                lengths.add(n % chunk)
        else:
            lengths.add(n)
    return sorted(lengths)
