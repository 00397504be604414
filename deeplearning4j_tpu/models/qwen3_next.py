"""Qwen3-Next-80B-A3B: a hybrid decoder, built from the keys of the published
`config.json` (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct,
`model_type` qwen3_next).

48 plain pre-norm residual blocks (zero-centred RMS norms): layer i mixes
tokens with gated attention on grouped k/v heads where `(i + 1) %
full_attention_interval == 0` and with a Gated DeltaNet (a recurrent layer
whose state is a matrix a head) otherwise; every block's MLP is
`num_experts` routed experts of `moe_intermediate_size` (softmax scores, top
`num_experts_per_tok`, renormalised) beside one shared expert of
`shared_expert_intermediate_size` under a sigmoid gate; a final norm and an
untied head. No auxiliary balancing loss and no multi-token-prediction
module: neither has a key in the config.

A `ComputationGraph` with one input, the ids (batch, time), and one output
scored against the next ids:

    net.fit_on_device(ids[:, :-1], ids[:, 1:], steps=n)

`share` cuts what one chip of a layer group holds, at the published widths:
`{"experts": 32, "vocab": 18992, "index": 0}` is chip `index` of 16 that
share each layer's 512 experts (the table and the head over 8 of them); the
mixers are whole on every chip. `"train_gate": False` beside them takes the
chosen experts' weights as constants of the backward pass, for a share run
without its exchange (`RoutedExperts`).
"""
from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.common.enums import WeightInit
from deeplearning4j_tpu.models.zoo_model import ZooModel
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.decoder import (
    GatedAttention, GatedDeltaNet, PreNormResidual, RMSNorm, RoutedExperts,
    TokenCrossEntropyHead, TokenEmbedding)
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.updater.updaters import Adam

# the published config.json, without the keys that say nothing of the shape
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


class Qwen3Next(ZooModel):
    def __init__(self, config: Optional[dict] = None, seed: int = 123,
                 sequence_length: int = 8192, share: Optional[dict] = None,
                 updater=None, dtype: str = "float32",
                 compute_dtype: Optional[str] = "bfloat16", remat: bool = True,
                 init_std: float = 0.02):
        """`config`: the keys of a `config.json` of this `model_type`
        (default: the published one). `num_experts` and `vocab_size` are the
        published counts; `share` says what of them is held here."""
        self.config = dict(PUBLISHED if config is None else config)
        super().__init__(self.config["vocab_size"], seed)
        c = self.config
        if c.get("mlp_only_layers") or c.get("decoder_sparse_step", 1) != 1 \
                or c.get("rope_scaling") or c.get("use_sliding_window") \
                or c.get("tie_word_embeddings") \
                or c["shared_expert_intermediate_size"] % c["moe_intermediate_size"]:
            raise ValueError(
                "built: every layer's MLP routed experts beside a shared one of "
                "a whole number of expert widths, plain rotary frequencies, no "
                "window, an untied head")
        self.sequence_length = int(sequence_length)
        self.share = dict(share or {})
        self.updater = updater or Adam(learning_rate=3e-4, beta1=0.9, beta2=0.95,
                                       epsilon=1e-8)
        self.dtype, self.compute_dtype, self.remat = dtype, compute_dtype, remat
        self.init_std = init_std
        self.input_shape = (self.sequence_length,)

    # ------------------------------------------------------------ layers
    def _init(self) -> dict:
        return {"weight_init": WeightInit.DISTRIBUTION,
                "dist": {"type": "normal", "mean": 0.0, "std": self.init_std}}

    def _held(self, what: str, published: int):
        """(held, first) of this share's experts or vocabulary rows."""
        held = int(self.share.get(what, published))
        return held, int(self.share.get("index", 0)) * held

    def _residual(self, sublayer) -> PreNormResidual:
        return PreNormResidual(layer=sublayer, eps=self.config["rms_norm_eps"],
                               **self._init())

    def _mixer(self, i: int):
        c = self.config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        if (i + 1) % c["full_attention_interval"] == 0:
            return GatedAttention(
                n_in=d, n_out=d, n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                rotary_dim=int(c["head_dim"] * c["partial_rotary_factor"]),
                rope_theta=float(c["rope_theta"]), eps=eps, **self._init())
        return GatedDeltaNet(
            n_in=d, n_out=d, n_k_heads=c["linear_num_key_heads"],
            n_v_heads=c["linear_num_value_heads"], d_k=c["linear_key_head_dim"],
            d_v=c["linear_value_head_dim"], conv_width=c["linear_conv_kernel_dim"],
            eps=eps, **self._init())

    def _experts(self) -> RoutedExperts:
        c = self.config
        d = c["hidden_size"]
        held, first = self._held("experts", c["num_experts"])
        return RoutedExperts(
            n_in=d, n_out=d, n_experts=c["num_experts"], experts_held=held,
            first_expert=first, top_k=c["num_experts_per_tok"],
            width=c["moe_intermediate_size"],
            n_shared=c["shared_expert_intermediate_size"] // c["moe_intermediate_size"],
            norm_topk_prob=bool(c["norm_topk_prob"]), scoring_func="softmax",
            shared_gate=True, train_gate=bool(self.share.get("train_gate", True)),
            **self._init())

    # ------------------------------------------------------------- graph
    def conf(self):
        c = self.config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        rows, first_row = self._held("vocab", c["vocab_size"])
        g = (NeuralNetConfiguration.Builder().seed(self.seed).dtype(self.dtype)
             .compute_dtype(self.compute_dtype).remat(self.remat)
             .updater(self.updater).graph_builder().add_inputs("ids"))
        g.add_layer("embed", TokenEmbedding(
            n_in=c["vocab_size"], n_out=d, rows_held=rows, first_row=first_row,
            **self._init()), "ids")
        cur = "embed"
        for i in range(c["num_hidden_layers"]):
            g.add_layer(f"b{i}_mix", self._residual(self._mixer(i)), cur)
            g.add_layer(f"b{i}_mlp", self._residual(self._experts()), f"b{i}_mix")
            cur = f"b{i}_mlp"
        g.add_layer("final_norm", RMSNorm(n_in=d, eps=eps, zero_centred=True), cur)
        g.add_layer("lm_head", TokenCrossEntropyHead(
            n_in=d, n_out=c["vocab_size"], rows_held=rows, first_row=first_row,
            **self._init()), "final_norm")
        return g.set_outputs("lm_head").set_input_types(
            InputType.feed_forward(self.sequence_length)).build()

    def init(self):
        return ComputationGraph(self.conf()).init()
