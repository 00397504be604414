"""Olmo-Hybrid-7B: a dense hybrid decoder, built from the keys of the
published `config.json` (https://huggingface.co/allenai/Olmo-Hybrid-7B,
`model_type` olmo_hybrid).

32 residual blocks with the Olmo family's norm order, the RMS norm (gain from
1) on each sublayer's *output*: h = x + norm(mixer(x)); y = h + norm(MLP(h)).
`layer_types` says which mixer a layer has: `linear_attention` is a Gated
DeltaNet (a recurrent layer whose state is a `linear_key_head_dim` x
`linear_value_head_dim` matrix a head, one value head a key head, the write
strength 2 sigmoid where `linear_allow_neg_eigval`), `full_attention` causal
softmax attention with an RMS norm over the whole width of q and of k, no
rotary (`rope_parameters.rope_theta` is null: the recurrent layers carry the
order), no gate and no bias. Every block's MLP is a dense SwiGLU of
`intermediate_size`; a final norm and an untied head.

A `ComputationGraph` with one input, the ids (batch, time), and one output
scored against the next ids:

    net.fit_on_device(ids[:, :-1], ids[:, 1:], steps=n)

`share` cuts what one chip of a layer group holds, at the published widths:
`{"heads": 15, "vocab": 12544, "index": 0}` is chip `index` of the 2 that
share every mixer's 30 heads (the table and the head over 8 chips); the MLP
is whole on every chip. nn/conf/layers/decoder.py says what a layer does with
its share.
"""
from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.common.enums import WeightInit
from deeplearning4j_tpu.models.zoo_model import ZooModel
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.decoder import (
    GatedAttention, GatedDeltaNet, GatedMLP, PreNormResidual, RMSNorm,
    TokenCrossEntropyHead, TokenEmbedding)
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.updater.updaters import Adam

_PERIOD = ["linear_attention"] * 3 + ["full_attention"]

# the published config.json, without the keys that say nothing of the shape
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": _PERIOD * 8, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}


class OlmoHybrid(ZooModel):
    def __init__(self, config: Optional[dict] = None, seed: int = 123,
                 sequence_length: int = 8192, share: Optional[dict] = None,
                 updater=None, dtype: str = "float32",
                 compute_dtype: Optional[str] = "bfloat16", remat: bool = True,
                 init_std: float = 0.02):
        """`config`: the keys of a `config.json` of this `model_type`
        (default: the published one). The head counts and `vocab_size` are
        the published ones; `share` says what of them is held here."""
        self.config = dict(PUBLISHED if config is None else config)
        super().__init__(self.config["vocab_size"], seed)
        c = self.config
        if c.get("attention_bias") or c.get("tie_word_embeddings") \
                or c["hidden_act"] != "silu" \
                or (c.get("rope_parameters") or {}).get("rope_theta") is not None \
                or c["hidden_size"] % c["num_attention_heads"] \
                or len(c["layer_types"]) != c["num_hidden_layers"]:
            raise ValueError(
                "built: no bias, an untied head, silu, no rotary, heads of "
                "hidden_size / num_attention_heads, a layer type a layer")
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

    def _heads(self, published: int) -> int:
        """This share's part of a mixer's `published` heads: the share names
        the attention's, and every mixer keeps the same part of its own."""
        of = self.config["num_attention_heads"]
        return published * int(self.share.get("heads", of)) // of

    def _residual(self, sublayer) -> PreNormResidual:
        return PreNormResidual(layer=sublayer, eps=self.config["rms_norm_eps"],
                               zero_centred=False, norm_output=True,
                               **self._init())

    def _mixer(self, kind: str):
        c = self.config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        if kind == "full_attention":
            return GatedAttention(
                n_in=d, n_out=d, n_heads=self._heads(c["num_attention_heads"]),
                n_kv_heads=self._heads(c["num_key_value_heads"]),
                head_dim=d // c["num_attention_heads"], rotary_dim=0, eps=eps,
                output_gate=False, qk_norm_whole=True, zero_centred=False,
                **self._init())
        if kind != "linear_attention":
            raise ValueError(f"layer type {kind!r}: linear_attention or "
                             "full_attention")
        return GatedDeltaNet(
            n_in=d, n_out=d, n_k_heads=self._heads(c["linear_num_key_heads"]),
            n_v_heads=self._heads(c["linear_num_value_heads"]),
            d_k=c["linear_key_head_dim"], d_v=c["linear_value_head_dim"],
            conv_width=c["linear_conv_kernel_dim"], eps=eps,
            beta_scale=2.0 if c["linear_allow_neg_eigval"] else 1.0,
            **self._init())

    # ------------------------------------------------------------- graph
    def conf(self):
        c = self.config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        rows = int(self.share.get("vocab", c["vocab_size"]))
        first_row = int(self.share.get("index", 0)) * rows
        g = (NeuralNetConfiguration.Builder().seed(self.seed).dtype(self.dtype)
             .compute_dtype(self.compute_dtype).remat(self.remat)
             .updater(self.updater).graph_builder().add_inputs("ids"))
        g.add_layer("embed", TokenEmbedding(
            n_in=c["vocab_size"], n_out=d, rows_held=rows, first_row=first_row,
            **self._init()), "ids")
        cur = "embed"
        for i, kind in enumerate(c["layer_types"]):
            g.add_layer(f"b{i}_mix", self._residual(self._mixer(kind)), cur)
            g.add_layer(f"b{i}_mlp", self._residual(GatedMLP(
                n_in=d, n_out=d, width=c["intermediate_size"], **self._init())),
                f"b{i}_mix")
            cur = f"b{i}_mlp"
        g.add_layer("final_norm", RMSNorm(n_in=d, eps=eps), cur)
        g.add_layer("lm_head", TokenCrossEntropyHead(
            n_in=d, n_out=c["vocab_size"], rows_held=rows, first_row=first_row,
            **self._init()), "final_norm")
        return g.set_outputs("lm_head").set_input_types(
            InputType.feed_forward(self.sequence_length)).build()

    def init(self):
        return ComputationGraph(self.conf()).init()
