"""Paged-KV inference model for MoE (Mixtral-family) architectures.

Reference analog: the mixtral / qwen2-moe policies in
``deepspeed/inference/v2/engine_factory.py:69`` and the MoE module stack —
``modules/implementations/moe/cutlass_multi_gemm.py`` (top-k gating +
moe_scatter + grouped GEMM + moe_gather) backed by
``kernels/cutlass_ops/moe_gemm`` and ``kernels/ragged_ops/{top_k_gating,
moe_scatter,moe_gather}``.

TPU-native form: the llama paged trunk (:class:`PagedInferenceModel`)
with the dense SwiGLU MLP swapped for dropless routed experts — fp32
router, top-k renormalised gates, tokens sorted by expert with one
``lax.ragged_dot`` grouped GEMM per projection (``ops/grouped_gemm.py``),
segment-sum combine. No capacity buffers, no token drops — serving
latency must not depend on routing luck.

Consumes ``models.mixtral.MixtralForCausalLM`` training params directly
(``layers_i/mlp/moe/{wg, experts/{w1,w2,w3}}``), so a trained Mixtral
checkpoint (or the hybrid engine's live training params) serves without a
conversion step.

Tensor parallelism: expert FFN dims shard on ``tensor`` exactly like the
dense path (w1/w3 column, w2 row, one psum after combine); the router is
replicated. The expert mesh axis is a *training* concern (a2a dispatch,
``moe/layer.py``) — serving shards experts' insides, not their identity,
matching the reference's TP-sharded MoE inference.
"""

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import PartitionSpec as P

from ..models.mixtral import MixtralConfig
from ..moe.dropless import routed_expert_ffn
from ..parallel.topology import TENSOR_AXIS
from .model import PagedInferenceModel, join_path


class PagedMoEModel(PagedInferenceModel):
    """Serves :class:`~..models.mixtral.MixtralConfig` checkpoints through
    the ragged engine (same ``forward_chunk`` / ``restore_kv`` / TP
    contract as the llama model)."""

    def __init__(self, cfg: MixtralConfig, params, **kw):
        if not isinstance(cfg, MixtralConfig):
            raise TypeError("PagedMoEModel needs a MixtralConfig")
        topo = kw.get("topology")
        quant = kw.get("quantization")
        if topo is not None and topo.tensor_size > 1 and quant is not None \
                and quant.enabled:
            # the ONLY rejection of TP+quantization (the base class
            # supports both int8 modes under TP via the k-major trunk
            # layout; expert stacks have no shard-aligned grouping)
            raise NotImplementedError(
                "tensor-parallel quantized serving is not available for "
                "the MoE family (expert-stack quantization groups are "
                "not shard-aligned)")
        super().__init__(cfg, params, **kw)

    def _validate_tp(self):
        super()._validate_tp()
        shared = getattr(self.cfg, "shared_expert_intermediate_size", 0)
        if shared and shared % self.tp:
            raise ValueError(
                f"shared_expert_intermediate_size={shared} not divisible "
                f"by tensor parallel degree {self.tp}")

    @staticmethod
    def _keep_fp32(path) -> bool:
        """The router weight stays fp32 (training gates run fp32,
        moe/layer.py:47; bf16 rounding of near-tie logits would select
        different experts at serve time than at train time)."""
        return str(getattr(path[-1], "key", path[-1])) == "wg"

    # -------------------------------------------------------------- #
    def _whole_layers(self, layers):
        """The experts' three stacks stay out of the layer scan: the
        grouped matmul reads a layer's experts out of the stacked leaf
        by its index (``ops/grouped_gemm.py``: sliced out for a custom
        call they are copied, 1.2 GB a layer at 128 experts of 2048 x
        768). Quantized stacks dequantize a layer at a time and stay in
        the scan."""
        if self.quantization:
            return layers, None
        moe = layers["mlp"]["moe"]
        rest = {k: v for k, v in moe.items() if k != "experts"}
        return ({**layers, "mlp": {**layers["mlp"], "moe": rest}},
                moe["experts"])

    def _with_whole(self, lp, whole, layer):
        moe = dict(lp["mlp"]["moe"], experts=whole, layer=layer)
        return {**lp, "mlp": {**lp["mlp"], "moe": moe}}

    def _mlp_out(self, lp, h2):
        return self._routed(lp, h2)[0]

    def _swiglu(self, p, h2):
        """A dense SwiGLU of ``gate_proj``/``up_proj``/``down_proj``: a
        shared expert, or a dense layer among sparse ones."""
        gate = self._mm(h2, p["gate_proj"]["kernel"])
        up = self._mm(h2, p["up_proj"]["kernel"])
        return self._mm(jax.nn.silu(gate) * up, p["down_proj"]["kernel"])

    def _mlp(self, lp, h2, lanes, pool_slots):
        """The expert layer; ``picks`` an expert took over the real
        positions (``engine.moe_stats()``) and ``router_in``, what the
        router read (a probed block lane's goes to the host with its
        logits rows)."""
        out, experts = self._routed(lp, h2)
        picks = self._picks(experts, lanes.flat_idx < pool_slots,
                            lp["mlp"]["moe"]["wg"].shape[-1])
        return out, {"picks": picks, "router_in": h2}

    @staticmethod
    def _picks(experts, valid, n_experts):
        """The positions routed to each expert ``[E]``: ``experts``
        ``[B * T, k]`` counted where ``valid`` ``[B, T]`` (the real
        positions of the lanes)."""
        valid = valid.reshape(-1, 1)                         # [B * T, 1]
        return jnp.zeros((n_experts,), jnp.int32).at[
            experts.reshape(-1)].add(
            jnp.broadcast_to(valid, experts.shape).reshape(-1)
            .astype(jnp.int32))

    def _routed(self, lp, h2):
        """``(output [B, T, d], experts picked [B * T, k])``."""
        moe = lp["mlp"]["moe"]
        B, T, d = h2.shape
        renorm = getattr(self.cfg, "norm_topk_prob", True)
        # the scope names the layer's operations in a profile; a device
        # trace names an operation by its HLO text, which carries the
        # attribute and not the scope
        with jax.named_scope("expert_ffn"), \
                set_xla_metadata(hds_layer="expert_ffn"):
            out, _aux, experts = routed_expert_ffn(
                h2.reshape(B * T, d), moe["wg"], moe["experts"]["w1"],
                moe["experts"]["w3"], moe["experts"]["w2"],
                self.cfg.top_k, renorm, layer=moe.get("layer"))
        out = out.reshape(B, T, d)
        if "shared_gate_proj" in moe:   # qwen2-moe shared expert
            gate = self._mm(h2, moe["shared_gate_proj"]["kernel"])
            up = self._mm(h2, moe["shared_up_proj"]["kernel"])
            shared = self._mm(jax.nn.silu(gate) * up,
                              moe["shared_down_proj"]["kernel"])
            sg = h2 @ moe["shared_expert_gate"]["kernel"]
            out = out + jax.nn.sigmoid(sg) * shared
        if self.tp > 1:   # row-parallel partial sum over expert ff shards
            out = jax.lax.psum(out, TENSOR_AXIS)
        return out, experts

    # -------------------------------------------------------------- #
    def _param_spec_tree(self, params=None):
        specs = super()._param_spec_tree(params)

        def fix(path, spec):
            joined = join_path(path)
            if "/moe/" in joined or joined.endswith("/wg"):
                if "shared" in joined:
                    # shared-expert kernels carry gate_proj/up_proj/
                    # down_proj in their names — the base col/row rules
                    # already classified them ("shared_expert_gate"
                    # matches neither and stays replicated)
                    return spec
                if "w1" in joined or "w3" in joined:
                    return P(None, None, None, TENSOR_AXIS)  # [L,E,d,f]
                if "w2" in joined:
                    return P(None, None, TENSOR_AXIS, None)  # [L,E,f,d]
                return P()                                   # router fp32
            return spec
        specs["layers"] = jax.tree_util.tree_map_with_path(
            fix, specs["layers"],
            is_leaf=lambda x: isinstance(x, P))
        return specs
