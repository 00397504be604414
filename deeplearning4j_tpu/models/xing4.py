"""Xing4.0-29B-A4B: the zoo's first decoder, built from the keys of the
published `config.json` (https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B,
`model_type` xing4_0).

40 blocks on `hc_mult` residual streams, each a latent attention and an MLP
under a manifold-constrained hyper-connection of their own: the leading
`first_k_dense_replace` MLPs are gated MLPs of `intermediate_size`, the rest
`n_routed_experts` routed experts of `moe_intermediate_size` (sigmoid scores,
top `num_experts_per_tok`, normalised, scaled) beside `n_shared_experts`
shared; the streams are summed before the final norm and the untied head.
`num_nextn_predict_layers` 1 adds the multi-token-prediction module: one more
block of the expert kind over W [RMSNorm(Emb[t_{i+1}]); RMSNorm(h_i)], with
its own final norm and the main model's table and head (tied nodes), its
loss added with weight `mtp_loss_weight`.

A `ComputationGraph` with two inputs, the ids (batch, time) and the next
ids (the labels, which the MTP module reads), and two outputs, both scored
against the labels:

    net.fit_on_device((ids[:, :-1], ids[:, 1:]), (ids[:, 1:], ids[:, 1:]), steps=n)

`share` cuts what one chip of a layer group holds, at the published widths:
`{"heads": 4, "experts": 8, "vocab": 16384, "index": 0}` is chip `index` of
the 8 that share each layer of the 32-head, 64-expert, 131072-row model
(nn/conf/layers/decoder.py says what a layer does with its share);
`"train_gate": False` beside them takes the chosen experts' weights as
constants of the backward pass (`RoutedExperts`; default: trained).
"""
from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.common.enums import WeightInit
from deeplearning4j_tpu.models.zoo_model import ZooModel
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.decoder import (
    GatedMLP, HyperConnection, LatentAttention, MTPInput, RMSNorm, RoutedExperts,
    TokenCrossEntropyHead, TokenEmbedding)
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.graph.vertices import HyperStreamsVertex, MergeVertex
from deeplearning4j_tpu.nn.updater.updaters import Adam

# the published config.json, without the keys that say nothing of the shape
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "moe_intermediate_size": 1024,
    "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072,
}


class Xing4(ZooModel):
    def __init__(self, config: Optional[dict] = None, seed: int = 123,
                 sequence_length: int = 4096, share: Optional[dict] = None,
                 updater=None, dtype: str = "float32",
                 compute_dtype: Optional[str] = "bfloat16", remat: bool = True,
                 mtp_loss_weight: float = 0.3, init_std: float = 0.02):
        """`config`: the keys of a `config.json` of this `model_type`
        (default: the published one). `num_attention_heads`,
        `n_routed_experts` and `vocab_size` are the published counts; `share`
        says what of them is held here."""
        self.config = dict(PUBLISHED if config is None else config)
        super().__init__(self.config["vocab_size"], seed)
        c = self.config
        if c.get("scoring_func", "sigmoid") != "sigmoid" or c.get("n_group", 1) != 1 \
                or c.get("topk_group", 1) != 1:
            raise ValueError("only the sigmoid gate without group limits "
                             "(n_group = topk_group = 1) is built")
        self.sequence_length = int(sequence_length)
        self.share = dict(share or {})
        self.updater = updater or Adam(learning_rate=3e-4, beta1=0.9, beta2=0.95,
                                       epsilon=1e-8)
        self.dtype, self.compute_dtype, self.remat = dtype, compute_dtype, remat
        self.mtp_loss_weight, self.init_std = mtp_loss_weight, init_std
        self.input_shape = (self.sequence_length,)

    # ------------------------------------------------------------ layers
    def _init(self) -> dict:
        return {"weight_init": WeightInit.DISTRIBUTION,
                "dist": {"type": "normal", "mean": 0.0, "std": self.init_std}}

    def _held(self, what: str, published: int):
        """(held, first) of this share's heads, experts or vocabulary rows."""
        held = int(self.share.get(what, published))
        return held, int(self.share.get("index", 0)) * held

    def _hyper(self, sublayer) -> HyperConnection:
        c = self.config
        return HyperConnection(
            layer=sublayer, n_streams=c["hc_mult"],
            sinkhorn_iters=c["hc_sinkhorn_iters"], hc_eps=c["hc_eps"],
            clamp_min=c["mhc_h_res_clamp_min"], clamp_max=c["mhc_h_res_clamp_max"],
            eps=c["rms_norm_eps"], **self._init())

    def _attention(self) -> LatentAttention:
        c = self.config
        held, _ = self._held("heads", c["num_attention_heads"])
        return LatentAttention(
            n_in=c["hidden_size"], n_out=c["hidden_size"],
            n_heads=c["num_attention_heads"], heads_held=held,
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
            rope_theta=float(c["rope_theta"]), rope_scaling=c.get("rope_scaling"),
            eps=c["rms_norm_eps"], **self._init())

    def _mlp(self, dense: bool):
        c = self.config
        d = c["hidden_size"]
        if dense:
            return GatedMLP(n_in=d, n_out=d, width=c["intermediate_size"],
                            **self._init())
        held, first = self._held("experts", c["n_routed_experts"])
        return RoutedExperts(
            n_in=d, n_out=d, n_experts=c["n_routed_experts"], experts_held=held,
            first_expert=first, top_k=c["num_experts_per_tok"],
            width=c["moe_intermediate_size"], n_shared=c["n_shared_experts"],
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            norm_topk_prob=bool(c["norm_topk_prob"]),
            train_gate=bool(self.share.get("train_gate", True)), **self._init())

    def _block(self, g, attn: str, mlp: str, dense: bool, inp: str) -> str:
        g.add_layer(attn, self._hyper(self._attention()), inp)
        g.add_layer(mlp, self._hyper(self._mlp(dense)), attn)
        return mlp

    # ------------------------------------------------------------- graph
    def conf(self):
        c = self.config
        d, n, eps = c["hidden_size"], c["hc_mult"], c["rms_norm_eps"]
        rows, first_row = self._held("vocab", c["vocab_size"])
        g = (NeuralNetConfiguration.Builder().seed(self.seed).dtype(self.dtype)
             .compute_dtype(self.compute_dtype).remat(self.remat)
             .updater(self.updater).graph_builder()
             .add_inputs("ids", "next_ids"))
        table = lambda: TokenEmbedding(n_in=c["vocab_size"], n_out=d,
                                       rows_held=rows, first_row=first_row,
                                       **self._init())
        head = lambda shift, weight: TokenCrossEntropyHead(
            n_in=d, n_out=c["vocab_size"], rows_held=rows, first_row=first_row,
            shift=shift, loss_weight=weight, **self._init())
        g.add_layer("embed", table(), "ids")
        g.add_vertex("streams", HyperStreamsVertex(n, "expand"), "embed")
        cur = "streams"
        for i in range(c["num_hidden_layers"]):
            cur = self._block(g, f"b{i}_attn", f"b{i}_mlp",
                              i < c["first_k_dense_replace"], cur)
        g.add_vertex("state", HyperStreamsVertex(n, "sum"), cur)
        g.add_layer("final_norm", RMSNorm(n_in=d, eps=eps), "state")
        g.add_layer("lm_head", head(0, 1.0), "final_norm")
        outputs = ["lm_head"]
        if c.get("num_nextn_predict_layers", 0):
            if c["num_nextn_predict_layers"] != 1:
                raise ValueError("one multi-token-prediction module is built")
            g.add_layer("mtp_embed", table(), "next_ids", tied_to="embed")
            g.add_vertex("mtp_cat", MergeVertex(axis=-1), "mtp_embed", "state")
            g.add_layer("mtp_in", MTPInput(n_in=2 * d, n_out=d, eps=eps,
                                           **self._init()), "mtp_cat")
            g.add_vertex("mtp_streams", HyperStreamsVertex(n, "expand"), "mtp_in")
            cur = self._block(g, "mtp_attn", "mtp_mlp", False, "mtp_streams")
            g.add_vertex("mtp_state", HyperStreamsVertex(n, "sum"), cur)
            g.add_layer("mtp_norm", RMSNorm(n_in=d, eps=eps), "mtp_state")
            g.add_layer("mtp_head", head(1, self.mtp_loss_weight), "mtp_norm",
                        tied_to="lm_head")
            outputs.append("mtp_head")
        ids = InputType.feed_forward(self.sequence_length)
        return g.set_outputs(*outputs).set_input_types(ids, ids).build()

    def init(self):
        return ComputationGraph(self.conf()).init()
