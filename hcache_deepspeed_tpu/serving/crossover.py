"""Restore-vs-recompute crossover policy for HCache re-entry.

Evicting a sequence to host latents is only half a policy — the other
half is how it comes BACK. Two re-entry mechanisms exist:

* **restore** (``restore_kv``): ship ``latent_bytes(T)`` over the host
  link and replay only the per-layer K/V projections — linear in T,
  plus a fixed per-layer-chunk dispatch overhead;
* **recompute**: re-prefill the full cached prefix — the whole
  transformer stack, with the attention term growing with T², but zero
  link bytes and one dispatch.

Neither dominates: at short prefixes the restore lane's fixed chunk
overhead loses to one cheap prefill; at long prefixes recompute's full
stack (and quadratic attention) loses to a link-bound linear ship.
:class:`RestoreCrossoverModel` puts the analytic forms side by side,

    restore_s(T)   = chunks(T) * chunk_overhead
                   + latent_bytes(T) / link_bw
                   + T / replay_rate * occ_penalty
    recompute_s(T) = (T / prefill_rate + attn_coeff * T^2) * occ_penalty

takes its rates from synced measurements (``observe_ship``,
``observe_prefill``, ``observe_replay``: the ``restore_crossover``
benchmark feeds them; the tracer's spans time asynchronous enqueues
and are never read back), and the scheduler consults
:meth:`decide` per preempted sequence instead of always restoring.
Both compute terms carry the same batch-occupancy penalty — a busy
batch slows replay and recompute alike but not the link, which shifts
the crossover toward restore exactly when the engine is loaded (the
fused computation/communication overlap argument of arXiv:2305.06942,
applied as a cost model).

Until ``min_samples`` prefill observations have landed the model
returns "restore" (the pre-policy default), so an uncalibrated server
behaves exactly like the old always-restore scheduler.
"""

from dataclasses import dataclass
from typing import Dict, Optional

#: span names mined for calibration samples


@dataclass
class CrossoverConfig:
    """Knobs for :class:`RestoreCrossoverModel` (documented in
    docs/serving.md)."""
    #: per replay-chunk dispatch overhead (host issue + device launch)
    chunk_overhead_s: float = 5e-4
    #: quadratic attention coefficient of recompute (s per token^2);
    #: 0 keeps recompute linear (matmul-dominated regime)
    attn_s_per_token2: float = 0.0
    #: occupancy penalty slope: compute terms scale by
    #: ``1 + occupancy_beta * occupancy``
    occupancy_beta: float = 1.0
    #: EMA smoothing for calibration samples
    ema_alpha: float = 0.25
    #: prefill-rate samples required before the model overrides the
    #: always-restore default
    min_samples: int = 1
    #: seed rates; <= 0 means "unknown until calibrated"
    link_bytes_per_s: float = 0.0
    prefill_tokens_per_s: float = 0.0
    replay_tokens_per_s: float = 0.0
    #: cross-replica migration hysteresis: migrating must beat staying
    #: by this factor before the router moves a request (1.0 = any
    #: saving justifies a move; >1 demands a margin so near-ties do not
    #: bounce payloads between replicas)
    migrate_hysteresis: float = 1.0


class RestoreCrossoverModel:
    """Analytic restore-vs-recompute cost model, calibrated online.

    ``profile`` comes from ``engine.restore_profile()``:
    ``latent_bytes_per_token``, ``n_layer``, ``replay_flops_frac``
    (used to derive a replay rate from the measured prefill rate when
    no direct replay samples exist), ``restore_chunk_layers`` /
    ``restore_chunk_bytes`` (to count chunks(T)).
    """

    def __init__(self, profile: Dict,
                 config: Optional[CrossoverConfig] = None):
        self.profile = dict(profile)
        self.config = config or CrossoverConfig()
        c = self.config
        self.link_bytes_per_s = float(c.link_bytes_per_s)
        self.prefill_tokens_per_s = float(c.prefill_tokens_per_s)
        self.replay_tokens_per_s = float(c.replay_tokens_per_s)
        self.samples = {"link": 0, "prefill": 0, "replay": 0}

    # ------------------------------------------------------------- #
    # calibration
    # ------------------------------------------------------------- #
    def _ema(self, cur: float, new: float) -> float:
        if cur <= 0:
            return new
        a = self.config.ema_alpha
        return (1 - a) * cur + a * new

    def observe_ship(self, nbytes: float, seconds: float) -> None:
        if nbytes <= 0 or seconds <= 0:
            return
        self.link_bytes_per_s = self._ema(self.link_bytes_per_s,
                                          nbytes / seconds)
        self.samples["link"] += 1

    def observe_prefill(self, tokens: float, seconds: float) -> None:
        if tokens <= 0 or seconds <= 0:
            return
        self.prefill_tokens_per_s = self._ema(self.prefill_tokens_per_s,
                                              tokens / seconds)
        self.samples["prefill"] += 1

    def observe_replay(self, tokens: float, seconds: float) -> None:
        """``tokens`` at FULL-stack granularity: tokens whose entire
        layer stack replayed in ``seconds``."""
        if tokens <= 0 or seconds <= 0:
            return
        self.replay_tokens_per_s = self._ema(self.replay_tokens_per_s,
                                             tokens / seconds)
        self.samples["replay"] += 1

    # ------------------------------------------------------------- #
    # the analytic forms
    # ------------------------------------------------------------- #
    def chunks(self, tokens: int) -> int:
        L = int(self.profile.get("n_layer", 1))
        C = int(self.profile.get("restore_chunk_layers", 0) or 0)
        if C <= 0:
            per_layer = tokens * self.profile[
                "latent_bytes_per_token"] / max(L, 1)
            cap = self.profile.get("restore_chunk_bytes",
                                   64 * 1024 * 1024)
            C = max(1, min(L, int(cap // max(per_layer, 1))))
        return -(-L // C)

    def _replay_rate(self) -> float:
        if self.replay_tokens_per_s > 0:
            return self.replay_tokens_per_s
        frac = float(self.profile.get("replay_flops_frac", 1.0))
        if self.prefill_tokens_per_s > 0 and frac > 0:
            # replay runs the QKV fraction of a full forward
            return self.prefill_tokens_per_s / frac
        return 0.0

    def _penalty(self, occupancy: float) -> float:
        occ = min(max(float(occupancy), 0.0), 1.0)
        return 1.0 + self.config.occupancy_beta * occ

    def restore_cost_s(self, tokens: int,
                       occupancy: float = 0.0) -> float:
        c = self.config
        cost = self.chunks(tokens) * c.chunk_overhead_s
        if self.link_bytes_per_s > 0:
            cost += tokens * self.profile["latent_bytes_per_token"] \
                / self.link_bytes_per_s
        rate = self._replay_rate()
        if rate > 0:
            cost += tokens / rate * self._penalty(occupancy)
        return cost

    def recompute_cost_s(self, tokens: int,
                         occupancy: float = 0.0) -> float:
        c = self.config
        cost = c.chunk_overhead_s       # one prefill dispatch
        if self.prefill_tokens_per_s > 0:
            cost += tokens / self.prefill_tokens_per_s \
                * self._penalty(occupancy)
        cost += c.attn_s_per_token2 * tokens * tokens \
            * self._penalty(occupancy)
        return cost

    @property
    def calibrated(self) -> bool:
        return self.samples["prefill"] >= self.config.min_samples and \
            self.prefill_tokens_per_s > 0

    # ------------------------------------------------------------- #
    # cross-replica migration (the per-link transfer-cost extension)
    # ------------------------------------------------------------- #
    def migrate_cost_s(self, tokens: int, dst_occupancy: float,
                       link_bytes_per_s: float) -> float:
        """Price a cross-replica migration of a ``tokens``-long cached
        prefix: ship ``latent_bytes(T)`` over the *inter-replica* link
        (``link_bytes_per_s`` — a fleet property, distinct from the
        host→HBM link the restore term prices), then restore on the
        destination at *its* occupancy."""
        xfer = 0.0
        if link_bytes_per_s > 0:
            xfer = tokens * self.profile["latent_bytes_per_token"] \
                / link_bytes_per_s
        return xfer + self.restore_cost_s(tokens, dst_occupancy)

    def handoff_cost_s(self, tokens: int, dst_occupancy: float,
                       tier_link_bytes_per_s: float) -> float:
        """Price a prefill→decode tier handoff: the same transfer +
        destination-restore form as :meth:`migrate_cost_s`, but over
        the **tier link** — the dedicated prefill→decode interconnect
        a disaggregated deployment provisions, priced separately from
        the general inter-replica rebalance link so the two transports
        stay individually attributable."""
        return self.migrate_cost_s(tokens, dst_occupancy,
                                   tier_link_bytes_per_s)

    def decide_migration(self, tokens: int, src_occupancy: float,
                         dst_occupancy: float,
                         link_bytes_per_s: float) -> str:
        """``"migrate"`` or ``"stay"`` — move the request iff transfer
        + destination restore beats restoring in place at the source's
        occupancy by the configured hysteresis margin. Uncalibrated ⇒
        ``"migrate"``: the caller only asks after a pressure gap
        triggered, and refusing on an uncalibrated model would disable
        rebalancing exactly when no telemetry exists yet."""
        if not self.calibrated:
            return "migrate"
        stay = self.restore_cost_s(tokens, src_occupancy)
        move = self.migrate_cost_s(tokens, dst_occupancy,
                                   link_bytes_per_s)
        if move * self.config.migrate_hysteresis <= stay:
            return "migrate"
        return "stay"

    # ------------------------------------------------------------- #
    # latent prefix broadcast (broadcast+restore vs re-prefill)
    # ------------------------------------------------------------- #
    def prefix_broadcast_cost_s(self, tokens: int,
                                dst_occupancy: float,
                                link_bytes_per_s: float) -> float:
        """Price shipping a ``tokens``-long warm prefix over the
        inter-replica latent wire and restoring it on the cold
        replica: the same transfer + destination-restore form as a
        migration — the HCache restore path used as a prefix-broadcast
        primitive."""
        return self.migrate_cost_s(tokens, dst_occupancy,
                                   link_bytes_per_s)

    def reprefill_cost_s(self, tokens: int,
                         occupancy: float = 0.0) -> float:
        """Price re-prefilling the same prefix from scratch on the
        cold replica (what every shared-prefix request pays without
        reuse) — the recompute form at the destination's occupancy."""
        return self.recompute_cost_s(tokens, occupancy)

    def decide_prefix_broadcast(self, tokens: int,
                                dst_occupancy: float,
                                link_bytes_per_s: float) -> str:
        """``"broadcast"`` or ``"reprefill"`` — ship the prefix once
        iff wire + destination restore beats one re-prefill of the
        prefix (with the migration hysteresis margin; the broadcast
        amortizes over every future sharer, so beating a SINGLE
        re-prefill is the conservative floor). Uncalibrated ⇒
        ``"broadcast"`` — the caller only asks after a warm hit, and
        refusing on an uncalibrated model would disable reuse exactly
        when no telemetry exists yet."""
        if not self.calibrated:
            return "broadcast"
        ship = self.prefix_broadcast_cost_s(tokens, dst_occupancy,
                                            link_bytes_per_s)
        if ship * self.config.migrate_hysteresis <= \
                self.reprefill_cost_s(tokens, dst_occupancy):
            return "broadcast"
        return "reprefill"

    def decide(self, tokens: int, occupancy: float = 0.0) -> str:
        """``"restore"`` or ``"recompute"`` — whichever the model
        prices cheaper for a ``tokens``-long cached prefix at the
        current batch ``occupancy``. Uncalibrated ⇒ ``"restore"`` (the
        pre-policy default)."""
        if not self.calibrated:
            return "restore"
        if self.restore_cost_s(tokens, occupancy) <= \
                self.recompute_cost_s(tokens, occupancy):
            return "restore"
        return "recompute"

    def summary(self) -> Dict:
        return {
            "link_bytes_per_s": round(self.link_bytes_per_s, 1),
            "prefill_tokens_per_s": round(self.prefill_tokens_per_s, 1),
            "replay_tokens_per_s": round(self._replay_rate(), 1),
            "samples": dict(self.samples),
            "calibrated": self.calibrated,
        }
